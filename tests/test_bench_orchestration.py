"""Wedge-resilience tests for the bench.py orchestrator.

Round-2 postmortem (VERDICT.md): a wedged chip turned the round's
deliverable into rc=124 with no JSON line.  These tests pin the contract
the orchestrator must keep — a hung phase still yields its landed rows,
and a sick chip still yields one parseable JSON line with
``"wedged": true`` — without touching any TPU (the hung phase is a stub).
"""

import importlib.util
import json
import os
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


@pytest.fixture()
def bench_mod():
    spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_rows_survive_timeout(bench_mod, monkeypatch):
    monkeypatch.setenv("BENCH_SELFTEST_HANG", "1")
    # window must cover phase-subprocess startup (importing jax, ~2-5s)
    # before the rows land and the hang begins
    rows, ok, detail = bench_mod._run_phase("selftest", False, timeout_s=20)
    assert not ok
    assert "timed out" in detail
    assert [r["n"] for r in rows] == [1, 2]


def test_phase_rows_complete(bench_mod, monkeypatch):
    monkeypatch.delenv("BENCH_SELFTEST_HANG", raising=False)
    rows, ok, detail = bench_mod._run_phase("selftest", False, timeout_s=30)
    assert ok and len(rows) == 2


def test_orchestrate_wedged_chip_emits_json(bench_mod, monkeypatch, capsys,
                                            tmp_path):
    from flashinfer_tpu import compile_guard

    monkeypatch.setattr(
        compile_guard, "probe",
        lambda timeout_s=0: {"healthy": False, "elapsed": 0.0,
                             "detail": "stub wedge"},
    )
    rc = bench_mod.orchestrate(sweep=False, bank=False)
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["wedged"] is True
    assert result["value"] == 0.0
    assert result["metric"] == "batch_decode_attention_bandwidth_bs64_ctx4k"


def test_orchestrate_hung_phase_partial_json(bench_mod, monkeypatch, capsys):
    from flashinfer_tpu import compile_guard

    monkeypatch.setattr(
        compile_guard, "probe",
        lambda timeout_s=0: {"healthy": True, "elapsed": 1.0, "detail": "ok"},
    )
    monkeypatch.setenv("BENCH_SELFTEST_HANG", "1")
    monkeypatch.setitem(bench_mod.PHASE_TIMEOUT_S, "selftest", 5)
    rc = bench_mod.orchestrate(sweep=False, bank=False, phases=["selftest"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["wedged"] is True  # phase timed out -> flagged, not rc=124


def test_bank_appends_record(bench_mod, tmp_path, monkeypatch):
    # _bank writes next to bench.py; point it at a temp copy instead
    import shutil

    tmp_bench = tmp_path / "bench.py"
    shutil.copy(_BENCH, tmp_bench)
    spec = importlib.util.spec_from_file_location("bench_tmp", str(tmp_bench))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._bank({"result": {"value": 1.0}, "rows": []})
    banked = (tmp_path / "BENCH_BANKED.md").read_text()
    assert "bench.py run" in banked and '"value": 1.0' in banked
