"""The reader of the program's compile log (``readers/compile_log.py``) on
a log made by hand through the program's own ticket, and the files of the
metrics that read the step's record one level down (ISSUE 39)."""

import json
import pathlib

import pytest

from flashinfer_tpu.obs import steploop
from readers import compile_log, steploop_phases

LAYERS = pathlib.Path(__file__).resolve().parent.parent / "layers"
TRACE, LOWER, BACKEND = steploop.COMPILE_SPAN_EVENTS


def _spec(name):
    with open(LAYERS / f"{name}.json") as f:
        return json.load(f)


def _step(step, t0, *, traced, trace=0.0, lower=0.0, backend=0.0,
          fetch=0.5, surface="ServingEngine"):
    t = steploop.begin(surface, now=t0, step=step)
    for event, dur in ((TRACE, trace), (LOWER, lower), (BACKEND, backend)):
        if dur:
            steploop._on_time_span(event, 50.0, 50.0 + dur, fun_name="f")
    t.dispatched(now=t0 + 1.0)
    t.enter("fetch", now=t0 + 1.0)
    t.done(now=t0 + 1.0 + fetch)
    t.commit(rung=8, traced=traced, now=t0 + 2.0)


@pytest.fixture()
def log():
    """Five steps of set-up (three compile, one of them another surface's)
    and a window from step 5 on, in which step 6 compiles."""
    steploop.reset(capacity=64, compile_log=True)
    _step(0, 0.0, traced=True, trace=1.0, lower=2.0, backend=4.0, fetch=0.5)
    _step(1, 10.0, traced=False)
    _step(2, 20.0, traced=True, trace=0.5, lower=0.25, backend=8.0,
          fetch=0.25)
    _step(3, 30.0, traced=True, trace=64.0, surface="ShardedServingStep")
    _step(4, 40.0, traced=False, backend=0.125, fetch=0.0625)
    steploop.reset(capacity=64)             # the harness, before its window
    _step(5, 50.0, traced=False)
    _step(6, 60.0, traced=True, trace=32.0, lower=32.0, backend=32.0)
    yield {"steps": [{"tokens": 0, "sampled": 0},        # an idle poll
                     {"step": 5, "tokens": 8}, {"step": 6, "tokens": 8}]}
    steploop.reset(compile_log=True)


def test_set_up_lines_are_counted_and_window_lines_are_not(log):
    got = {n: compile_log.read(log, _spec(f"setup.{n}"))
           for n in ("trace_s", "lower_s", "compile_s", "first_run_s")}
    assert got == {"trace_s": pytest.approx(1.5),
                   "lower_s": pytest.approx(2.25),
                   "compile_s": pytest.approx(12.125),
                   "first_run_s": pytest.approx(0.8125)}


def test_nothing_to_read_is_none(log, monkeypatch):
    spec = _spec("setup.trace_s")
    assert compile_log.read({"steps": []}, spec) is None
    assert compile_log.read({"steps": [{"tokens": 0}]}, spec) is None
    # a window that opens at the engine's first step has no set-up line
    assert compile_log.read({"steps": [{"step": 0}]}, spec) is None
    # the parent's program: no compile log
    monkeypatch.delattr(steploop, "compile_log")
    assert compile_log.read(log, spec) is None


def test_the_record_one_level_down_through_the_metrics_files():
    """Each new ``host.*`` file names spans the program writes; on a
    record that lacks them (the parent's) it reads nothing."""
    ms = {"admit": 1, "schedule": 2, "assemble": 3, "lower.build.l0": 4,
          "lower.build.l1": 5, "lower.build.decode": 6,
          "lower.build.window.l1": 7, "lower.build.window.decode": 8,
          "dispatch.upload.pack": 9, "dispatch.upload.put": 10,
          "dispatch.upload.cut": 11, "dispatch.call": 12, "gc": 13}
    rec = {"phases": {k: v / 1e3 for k, v in ms.items() if "." not in k
                      and k != "gc"},
           "spans": {k: v / 1e3 for k, v in ms.items() if "." in k
                     or k == "gc"}}
    quiet = {"phases": rec["phases"], "spans": dict(rec["spans"], gc=0.0)}
    ctx = {"steps": [{"ledger": rec}, {"ledger": quiet}]}
    want = {"admit_schedule_ms_p50": 3, "assemble_ms_p50": 3,
            "plan_l0_ms_p50": 4, "plan_l1_ms_p50": 5, "plan_decode_ms_p50": 6,
            "plan_window_ms_p50": 15, "handover_put_ms_p50": 19,
            "handover_cut_ms_p50": 11, "call_ms_p50": 12,
            "gc_ms_per_step": 6.5}      # a mean over all steps, 0.0 counted
    old = {"steps": [{"ledger": {"phases": rec["phases"], "spans": {
        "lower.build": 0.015, "dispatch.upload": 0.03,
        "dispatch.call": 0.012}}}]}
    for name, value in want.items():
        spec = _spec(f"host.{name}")
        assert steploop_phases.read(ctx, spec) == pytest.approx(value), name
        if name not in ("admit_schedule_ms_p50", "assemble_ms_p50",
                        "call_ms_p50"):
            assert steploop_phases.read(old, spec) is None, name
