"""Hardware graduation observatory tests (ISSUE 20).

The bring-up harness's whole value is what happens on a BAD day —
a Mosaic wedge mid-ladder — so the pins here run the ladder machinery
with injected runners/probers (no jax, no subprocesses) plus ONE real
subprocess wedge (the ``sim:wedge`` rung, which sleeps past its
timeout before importing anything heavy): exact-rung attribution,
quarantine emission, halt + pending remainder, ``--resume``
skip-completed semantics, chooser pruning of quarantined tactics, the
L006 measured-reference gate, and the journal ↔ banked-row join.

The full 29-rung interpret-mode ladder is exercised by
``obs bringup --selftest`` (the lint.yml gate), not here — tier-1 must
stay fast.
"""

import json
import os

import pytest

from flashinfer_tpu import tactics_blocklist
from flashinfer_tpu.obs import bringup


def _fake_rungs(n=4):
    return [{"rung_id": f"r{i}", "kind": "knob", "op": f"op{i}",
             "tactic": i, "driver": "rmsnorm",
             "bench_phases": [f"phase{i}"]} for i in range(n)]


def _runner_factory(calls, wedge_at=None, fail_at=None):
    def runner(rung, *, timeout_s, interpret, chip):
        calls.append(rung["rung_id"])
        if rung["rung_id"] == wedge_at:
            return {"outcome": "wedge", "wall_s": timeout_s,
                    "detail": "rung timed out (chip wedged?)"}
        if rung["rung_id"] == fail_at:
            return {"outcome": "fail", "wall_s": 0.1, "detail": "boom"}
        return {"outcome": "pass", "wall_s": 0.1, "detail": ""}
    return runner


def _healthy():
    return {"healthy": True, "elapsed": 0.1, "detail": "ok"}


def test_ladder_covers_every_registry_entry():
    rungs = bringup.build_ladder("v5e")
    assert bringup.coverage_problems(rungs) == []
    # the three registries each contribute: 22 mosaic_risks + 4
    # planners + 7 knob bindings
    kinds = {}
    for r in rungs:
        kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
    assert kinds == {"mosaic_risk": 22, "planner": 4, "knob": 7}
    # riskiest construct class first: every strided-lane rung precedes
    # every lane-slice rung precedes every cast rung
    order = [r["rule"] for r in rungs if r["kind"] == "mosaic_risk"]
    ranked = [bringup.RISK_ORDER[r] for r in order]
    assert ranked == sorted(ranked)


def test_wedge_attributes_quarantines_and_halts(tmp_path):
    journal = bringup.Journal(str(tmp_path / "j.jsonl"))
    qpath = str(tmp_path / "q.json")
    rungs = _fake_rungs(4)
    calls = []
    s = bringup.run_ladder(
        rungs, journal=journal, journal_id="jid-1", quarantine=qpath,
        runner=_runner_factory(calls, wedge_at="r1"), prober=_healthy,
        interpret=True, probe_every=0, verbose=False)
    # exact-rung attribution: r1 wedged, r0 passed, r2/r3 never ran
    assert s["wedged"] == ["r1"] and s["halted"]
    assert s["passed"] == 1 and s["pending"] == ["r2", "r3"]
    assert calls == ["r0", "r1"]
    q = json.loads(open(qpath).read())
    assert [e["rung_id"] for e in q] == ["r1"]
    # knob rungs carry the (op, tactic) pair and the poisoned phases
    assert q[0]["op"] == "op1" and q[0]["tactic"] == 1
    assert q[0]["bench_phases"] == ["phase1"]
    assert q[0]["journal_id"] == "jid-1"
    # journal: r2/r3 recorded pending, not silently dropped
    outcomes = journal.rung_outcomes()
    assert outcomes == {"r0": "pass", "r1": "wedge",
                        "r2": "pending", "r3": "pending"}


def test_resume_skips_passed_and_quarantined(tmp_path):
    journal = bringup.Journal(str(tmp_path / "j.jsonl"))
    qpath = str(tmp_path / "q.json")
    rungs = _fake_rungs(4)
    bringup.run_ladder(
        rungs, journal=journal, journal_id="jid-1", quarantine=qpath,
        runner=_runner_factory([], wedge_at="r1"), prober=_healthy,
        interpret=True, probe_every=0, verbose=False)
    # resume: r0 (passed) and r1 (quarantined) skipped, r2/r3 run
    calls = []
    s = bringup.run_ladder(
        rungs, journal=journal, journal_id="jid-1", quarantine=qpath,
        runner=_runner_factory(calls), prober=_healthy,
        interpret=True, probe_every=0, resume=True, verbose=False)
    assert calls == ["r2", "r3"]
    assert s["skipped"] == 2 and s["passed"] == 2 and not s["halted"]
    # a non-resume run re-runs everything but the quarantined rung
    calls2 = []
    bringup.run_ladder(
        rungs, journal=journal, journal_id="jid-2", quarantine=qpath,
        runner=_runner_factory(calls2), prober=_healthy,
        interpret=True, probe_every=0, resume=False, verbose=False)
    assert calls2 == ["r0", "r2", "r3"]


def test_unhealthy_probe_after_clean_rung_is_a_wedge(tmp_path):
    """A rung can exit 0 and still leave the chip wedged — the
    post-rung probe is the arbiter, and the wedge attributes to the
    rung that ran before it."""
    journal = bringup.Journal(str(tmp_path / "j.jsonl"))
    qpath = str(tmp_path / "q.json")
    probes = iter([_healthy(),
                   {"healthy": False, "elapsed": 0.1, "detail": "dead"}])
    s = bringup.run_ladder(
        _fake_rungs(3), journal=journal, journal_id="jid-1",
        quarantine=qpath, runner=_runner_factory([]),
        prober=lambda: next(probes), interpret=False, probe_every=1,
        verbose=False)
    assert s["wedged"] == ["r1"] and s["pending"] == ["r2"]
    assert [e["rung_id"] for e in json.loads(open(qpath).read())] == ["r1"]


def test_sim_wedge_subprocess_times_out(tmp_path):
    """The one real-subprocess pin: the sim rung sleeps past its
    timeout and _spawn_rung must kill it and report a wedge."""
    res = bringup._spawn_rung({"rung_id": bringup.SIM_WEDGE_RUNG},
                              timeout_s=1.0, interpret=True)
    assert res["outcome"] == "wedge"
    assert "timed out" in res["detail"]


def test_quarantined_tactic_pruned_from_choosers(tmp_path, monkeypatch):
    qpath = str(tmp_path / "q.json")
    open(qpath, "w").write(json.dumps([
        {"rung_id": "l009:decode.splits", "op": "decode.splits",
         "tactic": 4, "reason": "wedged", "journal_id": "jid-1"},
        {"rung_id": "l009:prefill.fused_ingest",
         "op": "prefill.fused_ingest", "tactic": "on",
         "reason": "wedged", "journal_id": "jid-1"},
    ]))
    monkeypatch.setenv("FLASHINFER_TPU_BRINGUP_QUARANTINE", qpath)
    monkeypatch.setattr(tactics_blocklist, "_bringup_cache", None)
    assert tactics_blocklist.blocked("decode.splits", 4)
    from flashinfer_tpu.obs import costmodel

    best, table = costmodel.choose_decode_splits(
        64, 4096, 32, 8, 128, hbm_tbps=0.8, candidates=(1, 2, 4))
    assert 4 not in table and {1, 2} <= set(table)
    use, ev = costmodel.predict_prefill_ingest_win(
        4096, 4096, 32, 8, 128, hbm_tbps=0.8)
    assert use is False and ev.get("pruned_quarantined") == 1.0
    # lifting the quarantine restores the candidate
    monkeypatch.delenv("FLASHINFER_TPU_BRINGUP_QUARANTINE")
    monkeypatch.setattr(tactics_blocklist, "_bringup_cache", None)
    _, table = costmodel.choose_decode_splits(
        64, 4096, 32, 8, 128, hbm_tbps=0.8, candidates=(1, 2, 4))
    assert 4 in table


def test_journal_joins_banked_rows_by_row_stamp():
    from flashinfer_tpu.obs import bench_audit

    row = {"phase": "decode_splits", "bs": 64, "ctx": 4096,
           "num_splits": 4, "us": 100.0, "tbps": 0.5}
    audited = bench_audit.RowAuditor().stamp(dict(row))
    # the stamp is derived from configuration identity only: recomputing
    # it over the stamped row (measurements and all) must round-trip
    assert audited["row_id"] == bench_audit.row_stamp(audited)
    assert audited["row_id"] == bench_audit.row_stamp(row)
    # measurement jitter does not move the join key
    noisy = dict(row, us=200.0, tbps=0.25)
    assert bench_audit.row_stamp(noisy) == audited["row_id"]
    # a different configuration does
    other = dict(row, num_splits=8)
    assert bench_audit.row_stamp(other) != audited["row_id"]


def test_graduate_flips_seed_to_measured(tmp_path):
    cfg_dir = tmp_path / "tuning_configs"
    cfg_dir.mkdir()
    key = "decode.splits|4096_256_32_8_128_16_16_bfloat16"
    (cfg_dir / "v5e.json").write_text(json.dumps({
        "decode": {"comment": "seeded", "seed": True,
                   "tactics": {key: 1, "decode.splits|other_shape": 1}},
    }))
    emit = tmp_path / "emit.json"
    emit.write_text(json.dumps({
        "decode": {"comment": "measured sweep", "seed": False,
                   "tactics": {key: 4}}}))
    banked = tmp_path / "BENCH_BANKED.md"
    row = {"phase": "decode_splits", "bs": 64, "num_splits": 4,
           "us": 50.0}
    banked.write_text("```json\n" + json.dumps({"rows": [row]})
                      + "\n```\n")
    journal = bringup.Journal(str(tmp_path / "j.jsonl"))
    g = bringup.graduate(
        [str(emit)], chip="v5e", journal=journal, journal_id="jid-9",
        configs_dir=str(cfg_dir), banked_path=str(banked))
    assert g["graduated"] == ["decode"]
    sec = json.loads((cfg_dir / "v5e.json").read_text())["decode"]
    from flashinfer_tpu.obs import bench_audit

    assert sec["provenance"] == "measured"
    assert sec["journal_id"] == "jid-9"
    assert sec["banked_row"] == [bench_audit.row_stamp(row)]
    assert "seed" not in sec
    # the measured winner replaced the seed value; the unmeasured key
    # survives and is labeled
    assert sec["tactics"][key] == 4
    assert sec["seed_keys"] == ["decode.splits|other_shape"]
    # journaled
    assert journal.step_outcomes("graduate") == {"decode": "pass"}


def test_graduate_refuses_without_banked_rows(tmp_path):
    cfg_dir = tmp_path / "tuning_configs"
    cfg_dir.mkdir()
    (cfg_dir / "v5e.json").write_text(json.dumps({
        "decode": {"seed": True, "tactics": {"decode.splits|s": 1}}}))
    emit = tmp_path / "emit.json"
    emit.write_text(json.dumps({
        "decode": {"tactics": {"decode.splits|s": 2}}}))
    banked = tmp_path / "BENCH_BANKED.md"
    banked.write_text("no rows here\n")
    g = bringup.graduate(
        [str(emit)], chip="v5e",
        journal=bringup.Journal(str(tmp_path / "j.jsonl")),
        journal_id="jid-9", configs_dir=str(cfg_dir),
        banked_path=str(banked))
    assert g["graduated"] == []
    assert g["skipped"] and "no banked rows" in g["skipped"][0]["reason"]
    # config untouched: an unauditable flip never lands
    sec = json.loads((cfg_dir / "v5e.json").read_text())["decode"]
    assert sec.get("seed") is True and "provenance" not in sec


def _staged_project(tmp_path, payload):
    from flashinfer_tpu.analysis.core import Project

    pkg = tmp_path / "pkg"
    (pkg / "tuning_configs").mkdir(parents=True)
    (pkg / "mod.py").write_text("x = 1\n")
    (pkg / "tuning_configs" / "gen.json").write_text(json.dumps(payload))
    return Project.from_paths([str(pkg)])


def test_l006_requires_references_on_measured_sections(tmp_path):
    from flashinfer_tpu.analysis import tuning_schema

    good = {"decode": {"provenance": "measured", "journal_id": "jid-1",
                       "banked_row": ["abc123def456"],
                       "tactics": {}}}
    assert tuning_schema.run(_staged_project(tmp_path, good)) == []
    for missing in ("journal_id", "banked_row"):
        bad = {"decode": dict(good["decode"])}
        del bad["decode"][missing]
        findings = tuning_schema.run(
            _staged_project(tmp_path / missing, bad))
        assert any(missing in f.message for f in findings), missing
    # empty reference list is as unfalsifiable as a missing one
    empty = {"decode": dict(good["decode"], banked_row=[])}
    findings = tuning_schema.run(_staged_project(tmp_path / "e", empty))
    assert any("banked_row" in f.message for f in findings)
    # seed sections need no references
    seed = {"decode": {"provenance": "seed", "tactics": {}}}
    assert tuning_schema.run(_staged_project(tmp_path / "s", seed)) == []


def test_quarantined_bench_phases_surface(tmp_path, monkeypatch):
    qpath = str(tmp_path / "q.json")
    open(qpath, "w").write(json.dumps([
        {"rung_id": "l015:cast:_mla_decode_kernel",
         "reason": "wedged", "bench_phases": ["mla"]},
        {"rung_id": "l009:decode.splits", "op": "decode.splits",
         "tactic": 4, "reason": "wedged",
         "bench_phases": ["decode_splits"]},
    ]))
    monkeypatch.setenv("FLASHINFER_TPU_BRINGUP_QUARANTINE", qpath)
    monkeypatch.setattr(tactics_blocklist, "_bringup_cache", None)
    try:
        assert sorted(bringup.quarantined_bench_phases()) == \
            ["decode_splits", "mla"]
    finally:
        monkeypatch.setattr(tactics_blocklist, "_bringup_cache", None)


def test_perf_report_graduation_section():
    from flashinfer_tpu.obs.roofline import (build_perf_report,
                                             render_perf_report)

    report = build_perf_report([])
    assert report["schema"] == "flashinfer_tpu.obs.perf/6"
    grad = report["graduation"]
    shipped = {(s["chip"], s["section"]) for s in grad["sections"]}
    assert ("v5e", "decode") in shipped
    assert all(s["status"] in ("pending", "measured", "quarantined")
               for s in grad["sections"])
    assert grad["audit"]["serving_ici"]["predicted_schema"] == "perf/2"
    assert "graduation (hardware bring-up pipeline)" \
        in render_perf_report(report)


@pytest.mark.quick
def test_doctor_summary_never_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_BRINGUP_JOURNAL",
                       str(tmp_path / "nope" / "j.jsonl"))
    d = bringup.doctor_summary()
    assert d["journal_entries"] == 0 and d["session"] is None
    assert "v5e" in d["seed_sections_remaining"]
