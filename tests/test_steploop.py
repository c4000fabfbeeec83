"""Step-loop flight deck (ISSUE 17): host/device overlap ledger, its
phases also spans on the profiler's clock (ISSUE 24).

Covers the zero-overhead default (gate-off facade no-op IN-PROCESS plus
the SUBPROCESS pin that plain library serving never even imports
``obs.steploop``), the ticket/ledger math on hand-driven clocks (gap
chaining, host_frac / overlap efficiency / Amdahl ceiling, phases with
children, ``fetch`` and ``post``), the bounded-ring and thread-safety
contracts, negative-gap and idle-tick semantics, the unified-trace step
lanes, the engine / ShardedServingStep wiring (sub-phases, device lane, the
``engine.*`` spans in a ``jax.profiler`` trace), the
``python -m flashinfer_tpu.obs steploop --selftest`` acceptance gate,
and the perf/6 report's ``host_loop`` section (banked-row Amdahl projection +
the live ledger join).  ISSUE 39: children of children, the collector's
hook and the compiler's listeners driven by hand, the compile log, ``t_end``
and ``slowest``.
"""

import gc
import json
import os
import subprocess
import sys
import threading

import pytest

from flashinfer_tpu import obs
from flashinfer_tpu.obs import export, steploop

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))


@pytest.fixture()
def fresh_ledger():
    """A fresh ledger, and no collection of the interpreter's own while a
    hand clock runs (the hook, once installed, would book it)."""
    steploop.reset(capacity=64)
    gc.disable()
    yield
    gc.enable()
    steploop.reset()


@pytest.fixture()
def gate_on(monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_STEPLOOP", "1")
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    obs.reset()
    steploop.reset(capacity=256)
    yield
    steploop.reset()
    obs.reset()


# ------------------------------------------------------- zero overhead --


@pytest.mark.quick
def test_gate_off_facade_is_none(monkeypatch):
    monkeypatch.delenv("FLASHINFER_TPU_STEPLOOP", raising=False)
    assert obs.steploop_enabled() is False
    assert obs.steploop_begin("X") is None
    assert obs.steploop_summary() is None
    monkeypatch.setenv("FLASHINFER_TPU_STEPLOOP", "1")
    tick = obs.steploop_begin("X")
    assert isinstance(tick, steploop.StepTicket)


_SUBPROC_PIN = r"""
import sys
import jax
import jax.numpy as jnp
from flashinfer_tpu.models import LlamaConfig, init_llama_params
from flashinfer_tpu.serve import EngineConfig, EngineRequest, ServingEngine

cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
eng = ServingEngine(cfg, init_llama_params(jax.random.PRNGKey(0), cfg),
                    EngineConfig(num_pages=8, page_size=8, max_batch=1,
                                 prefill_budget_tokens=8,
                                 max_seq_tokens=16))
eng.submit(EngineRequest("r0", [3, 1, 4], max_new_tokens=3))
assert len(eng.run()["r0"]) == 3
assert "flashinfer_tpu.obs.steploop" not in sys.modules, \
    "gate-off serving imported obs.steploop"
import gc
from jax._src import monitoring as mon  # the getters are not re-exported
hooks = [gc.callbacks, mon.get_event_duration_listeners(),
         mon.get_event_time_span_listeners(), mon.get_event_listeners()]
ours = [f for fs in hooks for f in fs  # jax keeps a gc callback of its own
        if type(f).__module__.startswith("flashinfer_tpu")
        or getattr(f, "__module__", "").startswith("flashinfer_tpu")]
assert ours == [], ours
print("PIN_OK")
"""


def test_zero_overhead_subprocess_pin():
    """THE zero-overhead pin: a plain gate-off serving loop (the
    wired ServingEngine surface) must finish without ``obs.steploop``
    ever entering sys.modules — the facade checks the gate BEFORE the
    import, so disabled processes pay nothing, not even module init: no
    ``gc`` callback, no ``jax.monitoring`` listener of the package's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLASHINFER_TPU_STEPLOOP", None)
    p = subprocess.run([sys.executable, "-c", _SUBPROC_PIN],
                       capture_output=True, text=True, env=env,
                       cwd=REPO_ROOT, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "PIN_OK" in p.stdout


# -------------------------------------------------- hand-clock ledger math --


def _three_step_lane():
    """Three steps on one lane with exact clocks:

    s1: host 0.2s (a=0.1 + dispatch=0.1), device 0.4s, no gap (first)
    s2: host 0.1s, gap 0.2s, device 0.4s
    s3: host 0.1s, gap 0.2s, device 0.4s

    steady-state pairs (s2, s3): Σgap=0.4, Σdevice=0.8 ->
    host_frac=1/3, overlap=2/3, amdahl=1.5.
    """
    t1 = steploop.begin("Lane", now=0.0)
    t1.mark("a", now=0.1)
    t1.dispatched(now=0.2)
    t1.done(now=0.6)
    t1.commit(tokens=4)

    t2 = steploop.begin("Lane", now=0.7)
    t2.dispatched(now=0.8)
    t2.done(now=1.2)
    r2 = t2.commit(tokens=4)

    t3 = steploop.begin("Lane", now=1.3)
    t3.dispatched(now=1.4)
    t3.done(now=1.8)
    r3 = t3.commit(tokens=4)
    return r2, r3


@pytest.mark.quick
def test_hand_clock_gap_and_overlap(fresh_ledger):
    r2, r3 = _three_step_lane()
    assert r2["gap_us"] == pytest.approx(0.2e6)
    assert r3["gap_us"] == pytest.approx(0.2e6)
    assert r2["device_us"] == pytest.approx(0.4e6)
    assert r2["host_us"] == pytest.approx(0.1e6)
    # the mark protocol books host phases only: ``spans`` holds the
    # collector's key, 0.0 on a step without a collection
    assert r2["spans"] == {"gc": 0.0} and r2["phases"] == {
        "dispatch": pytest.approx(0.1)}
    assert r2["gc_collections"] == [0, 0, 0] and not r2["compiled"]
    assert "pred_vs_measured" not in r2 and "predicted_s" not in r2

    s = steploop.summarize()
    assert s["steps"] == 3 and s["idle_ticks"] == 0
    assert s["surfaces"] == ["Lane"]
    assert s["missing_device_lane"] == 0 and s["negative_gaps"] == 0
    assert s["host_frac"] == pytest.approx(1.0 / 3.0)
    assert s["overlap_efficiency"] == pytest.approx(2.0 / 3.0)
    assert s["amdahl_ceiling"] == pytest.approx(1.5)
    # contiguous marks attribute the whole host window
    assert s["unattributed_frac"] == pytest.approx(0.0, abs=1e-9)
    assert s["phases"]["a"] == pytest.approx(0.1e6, abs=0.1)
    assert s["phases"]["dispatch"] == pytest.approx(0.3e6, abs=0.1)
    assert s["worst_phase"] == "dispatch"
    assert "drift" not in s


@pytest.mark.quick
def test_hand_clock_phases_children_fetch_and_post(fresh_ledger):
    """The engine's protocol on a hand clock: ``enter`` chains the
    top-level phases, ``phase`` books dotted children INSIDE the open
    one, and what follows the dispatch (``fetch``, ``post``) lies
    outside the host window.  The five host phases add up to what they
    added up to before the children existed."""
    t = steploop.begin("ServingEngine", now=10.0, step=7)
    t.enter("admit", now=10.0)
    t.enter("schedule", now=10.1)
    t.enter("assemble", now=10.3)
    t.enter("lower", now=10.6)
    b = t.phase("lower.build", now=10.6)
    b.close(now=11.0)
    u = t.phase("lower.upload", now=11.0)
    u.close(now=11.5)
    t.enter("dispatch", now=11.6)
    with t.phase("dispatch.upload", now=11.6) as du:
        pass                      # closed on the wall clock by the with
    c = t.phase("dispatch.call", now=11.8)
    c.close(now=12.0)
    t.dispatched(now=12.0)
    t.enter("fetch", now=12.0)
    t.done(now=15.0)
    t.enter("post", now=15.0)
    rec = t.commit(tokens=3, rung=8, traced=True, now=15.5)

    assert rec["phases"] == {
        "admit": pytest.approx(0.1), "schedule": pytest.approx(0.2),
        "assemble": pytest.approx(0.3), "lower": pytest.approx(1.0),
        "dispatch": pytest.approx(0.4)}
    assert sum(rec["phases"].values()) * 1e6 \
        == pytest.approx(rec["host_us"]) == pytest.approx(2.0e6)
    sp = rec["spans"]
    assert sp["lower.build"] == pytest.approx(0.4)
    assert sp["lower.upload"] == pytest.approx(0.5)
    assert sp["lower.build"] + sp["lower.upload"] \
        <= rec["phases"]["lower"] + 1e-9
    assert sp["dispatch.call"] == pytest.approx(0.2)
    assert sp["fetch"] == pytest.approx(3.0) \
        == pytest.approx(rec["device_us"] / 1e6)
    assert sp["post"] == pytest.approx(0.5)
    assert du.name == "dispatch.upload" and "dispatch.upload" in sp
    assert rec["t_dispatch"] == 12.0 and rec["t_done"] == 15.0
    assert rec["attrs"] == {"step": 7, "rung": 8, "traced": True}
    # the host accounting adds up the five phases and nothing twice
    s = steploop.summarize()
    assert set(s["phases"]) == {"admit", "schedule", "assemble", "lower",
                                "dispatch"}
    assert s["unattributed_frac"] == pytest.approx(0.0, abs=1e-9)
    assert s["worst_phase"] == "lower"


@pytest.mark.quick
def test_children_of_a_child_are_spans_and_counted_once(fresh_ledger):
    """What a callee books through ``under`` (``lower.build.l0``) goes to
    ``spans`` beside its parent; the host accounting adds up the phases
    alone, so nothing is counted twice, and ``t_end`` is the commit's."""
    t = steploop.begin("ServingEngine", now=0.0, step=0)
    t.enter("lower", now=0.0)
    build = t.phase("lower.build", now=0.0)
    span = t.under("lower.build")
    assert span("l0").name == "lower.build.l0"
    for name, (lo, hi) in {"l0": (0.0, 0.5), "l1": (0.5, 0.6),
                           "decode": (0.6, 0.8)}.items():
        t.phase(f"lower.build.{name}", now=lo).close(now=hi)
    t.phase("lower.build.l0", now=0.8).close(now=0.9)   # a second call adds
    build.close(now=1.0)
    t.enter("dispatch", now=1.0)
    up = t.under("dispatch.upload")
    assert up("cut").name == "dispatch.upload.cut"
    t.dispatched(now=1.5)
    t.done(now=2.0)
    rec = t.commit(now=2.25)
    assert rec["phases"] == {"lower": pytest.approx(1.0),
                             "dispatch": pytest.approx(0.5)}
    sp = rec["spans"]
    assert sp["lower.build.l0"] == pytest.approx(0.6)
    assert sp["lower.build.l1"] == pytest.approx(0.1)
    assert sp["lower.build.decode"] == pytest.approx(0.2)
    assert sp["lower.build.l0"] + sp["lower.build.l1"] \
        + sp["lower.build.decode"] <= sp["lower.build"] == pytest.approx(1.0)
    assert rec["t_end"] == 2.25 and rec["t_begin"] == 0.0
    s = steploop.summarize()
    assert set(s["phases"]) == {"lower", "dispatch"}
    assert sum(s["phases"].values()) == pytest.approx(rec["host_us"])
    assert s["unattributed_frac"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.quick
def test_collector_hook_by_hand_inside_and_outside_a_ticket(fresh_ledger):
    """The ``gc.callbacks`` hook on a hand clock: a collection that starts
    under an open ticket adds its seconds and its generation to that
    ticket, one between tickets to the module's counter; a step without
    one carries ``gc`` 0.0."""
    hook = steploop._collector
    hook("start", {"generation": 0}, now=0.0)        # before any ticket
    hook("stop", {"generation": 0}, now=0.25)
    t = steploop.begin("E", now=1.0)
    hook("start", {"generation": 0}, now=1.0)
    hook("stop", {"generation": 0}, now=1.125)
    hook("start", {"generation": 2}, now=1.5)        # also an engine.gc span
    hook("stop", {"generation": 2}, now=2.0)
    t.dispatched(now=2.0)
    t.done(now=2.5)
    r1 = t.commit(now=2.5)
    hook("start", {"generation": 1}, now=2.5)        # the harness's loop
    t2 = steploop.begin("E", now=2.75)               # opened while it runs
    hook("stop", {"generation": 1}, now=3.0)
    t2.dispatched(now=3.0)
    t2.done(now=3.5)
    r2 = t2.commit(now=3.5)
    hook("stop", {"generation": 0}, now=9.0)         # a stop with no start
    assert r1["spans"]["gc"] == pytest.approx(0.625)
    assert r1["gc_collections"] == [1, 0, 1]
    assert r2["spans"]["gc"] == 0.0 and r2["gc_collections"] == [0, 0, 0]
    g = steploop.summarize()["gc"]
    assert g["in_steps_s"] == pytest.approx(0.625)
    assert g["in_steps"] == [1, 0, 1]
    assert g["outside_s"] == pytest.approx(0.75) and g["outside"] == [1, 1, 0]
    steploop.reset()
    assert steploop.summarize()["gc"]["outside"] == [0, 0, 0]
    # the real collector reaches the hook once a ticket has installed it
    assert hook in gc.callbacks
    t3 = steploop.begin("E")
    gc.collect()
    t3.dispatched()
    t3.done()
    r3 = t3.commit()
    assert r3["gc_collections"][2] == 1 and r3["spans"]["gc"] > 0.0


TRACE, LOWER, BACKEND = steploop.COMPILE_SPAN_EVENTS


@pytest.mark.quick
def test_compiler_listeners_by_hand_union_not_sum(fresh_ledger):
    """The five event names through the listeners: a kind's seconds are
    the UNION of its time spans (a jit traced inside another fires inside
    the outer one's), the cache's retrieval is a plain duration, hits and
    misses are counted, the programs' names kept; outside a ticket
    nothing is booked."""
    steploop.reset(compile_log=True)
    steploop._on_time_span(TRACE, 0.0, 9.0, fun_name="nobody")  # no ticket
    t = steploop.begin("ServingEngine", now=100.0, step=4)
    t.enter("dispatch", now=100.0)
    call = t.phase("dispatch.call", now=100.0)
    # epoch seconds, as jax stamps them; nested and overlapping
    steploop._on_time_span(TRACE, 1000.0, 1004.0, fun_name="_body")
    steploop._on_time_span(TRACE, 1001.0, 1002.0, fun_name="inner")
    steploop._on_time_span(TRACE, 1003.5, 1005.0, fun_name="cut")
    steploop._on_time_span(TRACE, 1010.0, 1010.5, fun_name="late")
    steploop._on_time_span(LOWER, 1005.0, 1007.0, fun_name="jit(_body)")
    steploop._on_time_span(BACKEND, 1007.0, 1009.5, fun_name="jit(_body)")
    steploop._on_time_span("/jax/other", 0.0, 50.0, fun_name="x")
    steploop._on_duration(steploop.CACHE_LOAD_EVENT, 0.75)
    steploop._on_duration(steploop.CACHE_LOAD_EVENT, 0.25)
    steploop._on_duration("/jax/compilation_cache/compile_time_saved_sec",
                          7.0)
    steploop._on_event("/jax/compilation_cache/cache_hits")
    steploop._on_event("/jax/compilation_cache/cache_hits")
    steploop._on_event("/jax/compilation_cache/cache_misses")
    steploop._on_event("/jax/compilation_cache/tasks_using_cache")
    call.close(now=112.0)
    t.dispatched(now=112.0)
    t.enter("fetch", now=112.0)
    t.done(now=113.5)
    rec = t.commit(tokens=5, rung=16, traced=True, now=114.0)
    sp = rec["spans"]
    assert sp["compile.trace"] == pytest.approx(5.5)   # not 4+1+1.5+0.5
    assert sp["compile.lower"] == pytest.approx(2.0)
    assert sp["compile.backend"] == pytest.approx(2.5)
    assert sp["compile.cache_load"] == pytest.approx(1.0)
    assert rec["compiled"]
    (line,) = steploop.compile_log()
    assert line == {
        "surface": "ServingEngine", "step": 4, "rung": 16, "traced": True,
        "funs": ["jit(_body)"], "compile.trace": pytest.approx(5.5),
        "compile.lower": pytest.approx(2.0),
        "compile.backend": pytest.approx(2.5),
        "compile.cache_load": pytest.approx(1.0),
        "dispatch.call": pytest.approx(12.0), "fetch": pytest.approx(1.5),
        "hits": 2, "misses": 1, "t_begin": 100.0, "t_end": 114.0}
    steploop.reset(compile_log=True)


@pytest.mark.quick
def test_compile_log_lines_outlive_reset_and_slowest_orders(fresh_ledger):
    """A traced step leaves a line, a plain one none; the log outlives
    ``reset()`` (a harness resets before its window and reads set-up
    after it) and goes with ``reset(compile_log=True)``.  ``slowest``
    orders by ``t_end - t_begin``; ``steady`` leaves the log's steps out."""
    steploop.reset(compile_log=True)

    def one(step, t0, wall, *, traced=False, fire=False):
        t = steploop.begin("ServingEngine", now=t0, step=step)
        if fire:   # a compile with no trace: the handover's cut on a rung
            steploop._on_time_span(BACKEND, 5.0, 5.5, fun_name="jit(cut)")
        t.dispatched(now=t0 + wall / 2)
        t.done(now=t0 + wall)
        return t.commit(rung=8, traced=traced, now=t0 + wall)

    one(0, 0.0, 4.0, traced=True)
    one(1, 4.0, 0.5)
    one(2, 5.0, 3.0, fire=True)
    one(3, 8.0, 1.0)
    one(4, 9.0, 0.25)
    log = steploop.compile_log()
    assert [ln["step"] for ln in log] == [0, 2]
    assert [ln["traced"] for ln in log] == [True, False]
    assert log[0]["funs"] == [] and log[0]["compile.backend"] == 0.0
    assert log[1]["funs"] == ["jit(cut)"] \
        and log[1]["compile.backend"] == pytest.approx(0.5)
    assert steploop.summarize()["compile_log_lines"] == 2
    assert [r["attrs"]["step"] for r in steploop.slowest(3)] == [0, 2, 3]
    assert [r["attrs"]["step"] for r in steploop.slowest(3, steady=True)] \
        == [3, 1, 4]
    assert len(steploop.slowest(99)) == 5
    text = steploop.describe(steploop.slowest(1)[0])
    assert "step 0 rung 8" in text and "4000.00 ms" in text \
        and "gc 0.00" in text and "\n" not in text
    steploop.reset(capacity=64)
    assert steploop.ledger().total == 0
    assert [ln["step"] for ln in steploop.compile_log()] == [0, 2]
    steploop.reset(capacity=64, compile_log=True)
    assert steploop.compile_log() == []
    assert steploop.summarize()["compile_log_lines"] == 0


@pytest.mark.quick
def test_exit_report_under_the_gate_only(fresh_ledger, monkeypatch, capsys):
    """At exit, under the gate: the slowest steady steps and the compile
    log's totals, a line each on stderr; nothing with the gate off."""
    steploop.reset(capacity=64, compile_log=True)
    for i, wall in enumerate((2.0, 0.125, 0.5)):
        t = steploop.begin("ServingEngine", now=10.0 * i, step=i)
        t.dispatched(now=10.0 * i)
        t.done(now=10.0 * i + wall)
        t.commit(rung=8, traced=i == 0, now=10.0 * i + wall)
    monkeypatch.delenv("FLASHINFER_TPU_STEPLOOP", raising=False)
    steploop._exit_report()
    assert capsys.readouterr().err == ""
    monkeypatch.setenv("FLASHINFER_TPU_STEPLOOP", "1")
    steploop._exit_report()
    lines = capsys.readouterr().err.splitlines()
    assert len(lines) == 3 and all(ln.startswith("[steploop] ")
                                   for ln in lines)
    assert "slowest 1: ServingEngine step 2 " in lines[0] \
        and "500.00 ms" in lines[0]
    assert "slowest 2: ServingEngine step 1 " in lines[1]
    assert "compile log: 1 steps" in lines[2]
    # what runs at exit: nothing once a caller has reset the ledger (it
    # reads it itself, and its own last lines on stderr stay the last)
    assert steploop._RESET_SEEN
    steploop._at_exit()
    assert capsys.readouterr().err == ""
    monkeypatch.setattr(steploop, "_RESET_SEEN", False)
    steploop._at_exit()
    assert len(capsys.readouterr().err.splitlines()) == 3
    steploop.reset(compile_log=True)


@pytest.mark.quick
def test_idle_ticks_counted_but_do_not_break_gap_chain(fresh_ledger):
    t1 = steploop.begin("E", now=0.0)
    t1.dispatched(now=0.1)
    t1.done(now=0.5)
    t1.commit()
    # an empty-schedule poll between two real steps
    ti = steploop.begin("E", now=0.6)
    ri = ti.commit(idle=True)
    t2 = steploop.begin("E", now=0.9)
    t2.dispatched(now=1.0)
    t2.done(now=1.4)
    r2 = t2.commit()
    assert ri["idle"] is True and ri["gap_us"] is None
    # the gap still chains across the idle tick: 1.0 - 0.5
    assert r2["gap_us"] == pytest.approx(0.5e6)
    s = steploop.summarize()
    assert s["steps"] == 2 and s["idle_ticks"] == 1


@pytest.mark.quick
def test_negative_gap_is_surfaced_not_hidden(fresh_ledger):
    t1 = steploop.begin("N", now=0.0)
    t1.dispatched(now=0.1)
    t1.done(now=1.0)
    t1.commit()
    # next dispatch stamped BEFORE the previous done (clock skew)
    t2 = steploop.begin("N", now=0.2)
    t2.dispatched(now=0.3)
    t2.done(now=1.2)
    r2 = t2.commit()
    assert r2["gap_us"] == pytest.approx(-0.7e6)
    s = steploop.summarize()
    assert s["negative_gaps"] == 1


def test_gap_chain_is_per_surface_and_thread(fresh_ledger):
    ta = steploop.begin("A", now=0.0)
    ta.dispatched(now=0.1)
    ta.done(now=0.5)
    ta.commit()
    # a DIFFERENT surface on the same thread: no chain to A
    tb = steploop.begin("B", now=0.6)
    tb.dispatched(now=0.7)
    tb.done(now=1.0)
    rb = tb.commit()
    assert rb["gap_us"] is None
    ta2 = steploop.begin("A", now=1.1)
    ta2.dispatched(now=1.2)
    ta2.done(now=1.5)
    ra2 = ta2.commit()
    assert ra2["gap_us"] == pytest.approx(0.7e6)


# ----------------------------------------------------- ring + threading --


@pytest.mark.quick
def test_ring_bound_retains_newest_and_counts_drops():
    steploop.reset(capacity=4)
    try:
        for i in range(7):
            t = steploop.begin("R", now=float(i))
            t.dispatched(now=i + 0.1)
            t.done(now=i + 0.2)
            t.commit(tokens=i)
        led = steploop.ledger()
        assert led.total == 7 and led.dropped() == 3
        recs = led.records()
        assert len(recs) == 4
        assert [r["seq"] for r in recs] == [3, 4, 5, 6]  # newest kept
        s = steploop.summarize()
        assert s["steps"] == 4 and s["total"] == 7 and s["dropped"] == 3
    finally:
        steploop.reset()


def test_ledger_thread_safety_exact_totals():
    steploop.reset(capacity=10_000)
    try:
        N, K = 8, 250

        def work(tid):
            for i in range(K):
                t = steploop.begin(f"T{tid}", now=float(i))
                t.dispatched(now=i + 0.1)
                t.done(now=i + 0.2)
                t.commit()

        threads = [threading.Thread(target=work, args=(n,))
                   for n in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        led = steploop.ledger()
        assert led.total == N * K and led.dropped() == 0
        assert len(led.records()) == N * K
        # every record committed exactly once, seq is a permutation
        assert sorted(r["seq"] for r in led.records()) \
            == list(range(N * K))
    finally:
        steploop.reset()


# ------------------------------------------------------------ trace lanes --


@pytest.mark.quick
def test_trace_events_merge_into_valid_unified_trace(fresh_ledger):
    _three_step_lane()
    ti = steploop.begin("Lane", now=2.0)
    ti.commit(idle=True)
    evts = steploop.trace_events()
    names = [e["name"] for e in evts]
    assert "Lane.a" in names and "Lane.dispatch" in names
    assert names.count("Lane.device") == 3
    assert "Lane.idle" in names
    host = [e for e in evts if e.get("tid") == steploop.TRACE_TID_HOST
            and e["ph"] == "X"]
    dev = [e for e in evts if e.get("tid") == steploop.TRACE_TID_DEVICE
           and e["ph"] == "X"]
    assert host and len(dev) == 3
    assert all(e["cat"] == "steploop" for e in host + dev)
    # device windows carry the join args for trace tooling
    assert all({"tokens", "seq"} <= set(e["args"]) for e in dev)
    # the whole lane set merges into a schema-valid unified trace
    trace = export.to_unified_chrome_trace({}, extra_events=evts)
    assert export.validate_chrome_trace(trace) == []


@pytest.mark.quick
def test_registry_mirror_from_committed_records(gate_on):
    _three_step_lane()
    snap = obs.snapshot()
    assert sum(snap["counters"]["steploop.steps"].values()) == 3
    assert "steploop.host_us" in snap["histograms"]
    assert "steploop.device_us" in snap["histograms"]
    assert "steploop.gap_us" in snap["histograms"]
    assert "steploop.pred_vs_measured" not in snap["histograms"]
    t = steploop.begin("Lane", now=2.0)
    t.enter("lower", now=2.0)
    t.phase("lower.build", now=2.0).close(now=2.1)
    t.dispatched(now=2.2)
    t.enter("fetch", now=2.2)
    t.done(now=2.5)
    t.commit(now=2.5)
    phases = obs.snapshot()["histograms"]["steploop.phase_us"]
    # host phases, children and fetch all mirror under their own label
    for label in ("dispatch", "lower", "lower.build", "fetch"):
        assert any(f"phase={label}," in k or k.endswith(f"phase={label}")
                   for k in phases), (label, sorted(phases))


# ------------------------------------------------------- surface wiring --


def _tiny_engine(jnp):
    import jax

    from flashinfer_tpu.models import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    return cfg, ServingEngine(cfg, params, EngineConfig(
        num_pages=64, page_size=8, max_batch=2,
        prefill_budget_tokens=16, max_seq_tokens=32,
        sampling=SamplingConfig(temperature=0.8, top_k=8)))


@pytest.mark.quick
def test_engine_wiring_phases_idle_and_traced(gate_on):
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.serve import EngineRequest

    steploop.reset(capacity=256, compile_log=True)
    cfg, eng = _tiny_engine(jnp)
    # an empty-schedule poll is an EXPLICIT idle tick, not silence
    eng.step()
    assert eng.idle_steps == 1
    assert steploop.ledger().idle_total == 1
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.submit(EngineRequest(
            f"r{i}", [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
            max_new_tokens=3))
    eng.run()
    s = steploop.summarize()
    assert s["surfaces"] == ["ServingEngine"]
    assert s["steps"] >= 3 and s["missing_device_lane"] == 0
    # the engine decomposes into the full named sub-phase set
    assert {"admit", "schedule", "assemble", "lower", "dispatch"} \
        <= set(s["phases"])
    assert s["unattributed_frac"] < 0.01
    recs = [r for r in steploop.ledger().records() if not r["idle"]]
    # children, fetch and post are booked beside the five, per step, the
    # handover's parts under its span, and the collector's key
    for r in recs:
        sp = r["spans"]
        assert {"dispatch.upload", "dispatch.call", "fetch", "post", "gc",
                "dispatch.upload.pack", "dispatch.upload.put",
                "dispatch.upload.cut"} <= set(sp)
        assert sp["dispatch.upload"] + sp["dispatch.call"] \
            <= r["phases"]["dispatch"]
        assert sp["dispatch.upload.pack"] + sp["dispatch.upload.put"] \
            + sp["dispatch.upload.cut"] <= sp["dispatch.upload"]
        assert r["t_end"] >= r["t_done"] + sp["post"] - 1e-4
        # fetch opens a few microseconds after the dispatch returned
        assert r["spans"]["fetch"] * 1e6 == pytest.approx(r["device_us"],
                                                          abs=1e3)
    # the record carries the engine's step index, and says which steps
    # traced (compiled): each rung's first, and no other
    assert [r["attrs"]["step"] for r in recs] == list(range(len(recs)))
    traced = [r for r in recs if r["attrs"].get("traced")]
    assert len(traced) == eng.num_traces \
        == len({r["attrs"]["rung"] for r in recs})
    # each rung's first step is in the compile log with what the compiler
    # said of it, and so are its seconds in the step's record
    log = {ln["step"]: ln for ln in steploop.compile_log()}
    for r in traced:
        ln = log[r["attrs"]["step"]]
        assert ln["traced"] and ln["rung"] == r["attrs"]["rung"]
        assert ln["compile.trace"] > 0 and ln["compile.lower"] > 0 \
            and ln["compile.backend"] > 0
        assert any("_body" in f for f in ln["funs"]), ln["funs"]
        assert r["spans"]["compile.trace"] == ln["compile.trace"]
        assert ln["compile.trace"] + ln["compile.lower"] \
            + ln["compile.backend"] <= ln["t_end"] - ln["t_begin"]
        assert ln["dispatch.call"] == r["spans"]["dispatch.call"]
    assert all(ln["traced"] or ln["funs"] for ln in log.values())
    plain = [r for r in recs if not r["compiled"]]
    assert plain and not any("compile.trace" in r["spans"] for r in plain)
    assert steploop.slowest(1)[0]["compiled"]
    assert not steploop.slowest(1, steady=True)[0]["compiled"]
    steploop.reset(compile_log=True)
    snap = obs.snapshot()
    assert sum(snap["counters"]["engine.idle_steps"].values()) == 1


def test_engine_wiring_planner_children_kernel_tier_and_window(gate_on):
    """The kernel tier of a tiny model with window layers beside full
    ones (one rung, so one compile): the planner's levels under
    ``lower.build``, the window plan's under ``lower.build.window``, and
    neither sums to more than ``lower.build``, which holds both calls."""
    import dataclasses
    import pathlib
    import sys

    import jax
    import jax.numpy as jnp
    import numpy as np

    root = pathlib.Path(REPO_ROOT)
    for p in (root, root / "perfbench"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from families import afmoe as fam

    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    with open(root / "perfbench" / "configs" / "trinity-mini.json") as f:
        m = dict(json.load(f), **fam.TINY)
    m["sliding_window"] = 12
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          fam.make_params(7, m))
    eng = ServingEngine(
        dataclasses.replace(fam.model_config(m), dtype=jnp.float32), params,
        EngineConfig(num_pages=32, page_size=8, max_batch=2,
                     prefill_budget_tokens=12, max_seq_tokens=64,
                     ladder=(16,), sampling=SamplingConfig(top_k=1),
                     attention_backend="kernel"))
    rng = np.random.default_rng(0)
    eng.submit(EngineRequest(
        "r", rng.integers(1, m["vocab_size"], 20).tolist(),
        max_new_tokens=2))
    eng.run()
    recs = [r for r in steploop.ledger().records() if not r["idle"]]
    assert len(recs) >= 3 and eng.num_traces == 1
    main = ("lower.build.l0", "lower.build.l1", "lower.build.decode")
    window = ("lower.build.window.l1", "lower.build.window.decode")
    for r in recs:
        sp = r["spans"]
        assert set(main + window) <= set(sp)
        assert "lower.build.window.l0" not in sp     # a window plan has none
        assert sum(sp[n] for n in main + window) <= sp["lower.build"]
        assert sp["lower.build"] <= r["phases"]["lower"]
    # a callee with no span does what it did (the gate-off path)
    from flashinfer_tpu.serve import handover

    tree, sent, kib = handover.to_device({"a": np.arange(4, dtype=np.int32),
                                          "b": np.ones(2, np.float32)})
    assert sent == 2 and np.array_equal(tree["a"], np.arange(4))


def test_engine_phases_are_profiler_spans(gate_on, tmp_path):
    """Three tiny steps inside ``jax.profiler``: the host plane holds
    one ``engine.step`` span per step, labelled with the engine's step
    index (the same index as the record's and the facts'), and the
    phases and their children nested inside it."""
    import glob

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    from flashinfer_tpu.serve import EngineRequest

    cfg, eng = _tiny_engine(jnp)
    rng = np.random.default_rng(1)
    eng.submit(EngineRequest(
        "r0", [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
        max_new_tokens=5))
    eng.step()                     # the compile stays outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    facts = [eng.step() for _ in range(3)]
    jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for p in (ProfileData.from_file(found[0]).planes
                        if found else ())
              if p.name == "/host:CPU" for ln in p.lines
              for e in ln.events if e.name.startswith("engine.")]
    if not events:
        pytest.skip("this profiler writes no host plane with TraceMes")
    steps = sorted(e for e in events if e[0] == "engine.step")
    assert [e[3]["step"] for e in steps] == [f["step"] for f in facts] \
        == [1, 2, 3]
    recs = [r for r in steploop.ledger().records() if not r["idle"]][-3:]
    assert [r["attrs"]["step"] for r in recs] == [1, 2, 3]
    names = ("admit", "schedule", "assemble", "lower", "dispatch",
             "dispatch.upload", "dispatch.upload.pack",
             "dispatch.upload.put", "dispatch.upload.cut", "dispatch.call",
             "fetch", "post")
    for _, lo, hi, _ in steps:
        inside = {e[0] for e in events if lo <= e[1] and e[2] <= hi}
        assert {f"engine.{n}" for n in names} <= inside
    # a child lies inside its parent
    call = sorted(e for e in events if e[0] == "engine.dispatch.call")
    disp = sorted(e for e in events if e[0] == "engine.dispatch")
    assert all(d[1] <= c[1] and c[2] <= d[2] for c, d in zip(call, disp))
    # and a child's child inside the child
    cut = sorted(e for e in events if e[0] == "engine.dispatch.upload.cut")
    up = sorted(e for e in events if e[0] == "engine.dispatch.upload")
    assert len(cut) == len(up) >= 3
    assert all(u[1] <= c[1] and c[2] <= u[2] for c, u in zip(cut, up))


@pytest.mark.quick
def test_sharded_step_wiring_device_lane(gate_on, tiny_sharded_step):
    """The sharded step's ticket: ``signature`` + ``dispatch`` phases, a
    device lane on every step, steady-state gaps between them."""
    step, x0, layer_ws, head, head_s, state = tiny_sharded_step
    for _ in range(4):
        _, *state = step.run(x0, layer_ws, state[0], head, head_s,
                             *state[1:])
    s = steploop.summarize()
    assert s["surfaces"] == ["ShardedServingStep"]
    assert s["steps"] == 4 and s["missing_device_lane"] == 0
    assert {"signature", "dispatch"} <= set(s["phases"])
    assert s["negative_gaps"] == 0
    assert s["gap_us"]["count"] == 3  # steady-state pairs


# --------------------------------------------------------- CLI + perf/6 --


def test_steploop_selftest_cli_acceptance(tmp_path):
    """Acceptance: the engine's 9-step loop yields a ledger whose
    decomposition survives every selftest gate (device lane on all
    steps, zero negative gaps, attributed host time, wall-sum within
    5%) and a schema-valid unified trace with the step lanes."""
    out = str(tmp_path / "steploop_trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "steploop",
         "--selftest", "--steps", "9", "--out", out],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=560,
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    summary = json.loads(p.stdout[p.stdout.index("{"):])
    assert summary["problems"] == []
    s = summary["steploop"]
    assert s["steps"] == 9 and s["missing_device_lane"] == 0
    assert s["host_frac"] is not None and s["amdahl_ceiling"] >= 1.0
    assert abs(summary["decomposed_s"] - summary["loop_wall_s"]) \
        <= 0.05 * summary["loop_wall_s"]
    trace = json.load(open(out))
    lanes = {e.get("tid") for e in trace["traceEvents"]
             if e.get("cat") == "steploop"}
    assert {steploop.TRACE_TID_HOST, steploop.TRACE_TID_DEVICE} <= lanes


@pytest.mark.quick
def test_perf5_host_loop_section_and_live_join(fresh_ledger):
    from flashinfer_tpu.obs import costmodel, hwspec, roofline

    shape = costmodel.SERVING_SHAPES["llama70b_tp8shard_int8"]
    cost = costmodel.serving_step(64, 4096, 4, **shape)
    # a plausible wall: half of the v5e HBM roofline floor — the
    # auditor drops above-ceiling artifacts before _host_loop sees them
    t_s = cost.bytes_total / 0.819e12 / 0.5
    row = dict(phase="serving_fused", model="llama70b_tp8shard_int8",
               variant="fused", bs=64, ctx=4096, us_step=t_s * 1e6,
               host_gap_us=300.0, host_frac=0.25, pred_step_ratio=0.9)
    roofline.stamp_row(row, cost, t_s, hwspec.spec("v5e"),
                       step_mode="fused")
    _three_step_lane()  # the live ledger side
    rep = roofline.build_perf_report([row])
    assert rep["schema"] == "flashinfer_tpu.obs.perf/6"
    hl = rep["host_loop"]
    assert len(hl["rows"]) == 1
    m = hl["rows"][0]
    assert m["host_frac"] == 0.25
    assert m["amdahl_ceiling"] == pytest.approx(1.0 / 0.75, abs=1e-3)
    assert m["pred_step_ratio"] == 0.9
    assert hl["worst"]["host_frac"] == 0.25
    # the live join reads the already-loaded ledger (never imports)
    assert hl["live"]["steps"] == 3
    assert hl["live"]["amdahl_ceiling"] == pytest.approx(1.5)
    assert hl["live"]["worst_phase"] == "dispatch"
    assert "drift" not in hl["live"]
    text = roofline.render_perf_report(rep)
    assert "host loop" in text and "ceiling" in text
    assert "drift" not in text


@pytest.mark.quick
def test_doctor_host_loop_prints_collector_and_compile_log(fresh_ledger,
                                                           capsys):
    """``obs doctor`` in the serving process: the ``host_loop`` section
    carries the collector's totals and the compile log's line count."""
    import argparse

    from flashinfer_tpu.obs.__main__ import cmd_doctor

    steploop.reset(capacity=64, compile_log=True)
    t = steploop.begin("ServingEngine", now=0.0, step=0)
    steploop._collector("start", {"generation": 1}, now=0.0)
    steploop._collector("stop", {"generation": 1}, now=0.25)
    t.dispatched(now=0.5)
    t.done(now=1.0)
    t.commit(rung=8, traced=True, now=1.0)
    assert cmd_doctor(argparse.Namespace()) == 0
    out = capsys.readouterr().out
    hl = json.loads(out[out.index("{"):])["host_loop"]
    assert hl["steps"] == 1 and hl["compile_log_lines"] == 1
    assert hl["gc"]["in_steps_s"] == pytest.approx(0.25)
    assert hl["gc"]["in_steps"] == [0, 1, 0]
    steploop.reset(compile_log=True)


@pytest.mark.quick
def test_catalog_and_span_category_coverage():
    """Coverage gates stay closed: the steploop metrics are declared in
    the catalog (the doc-parity test then forces docs), the drift join
    is gone from it, the phase histogram's row names the new phases,
    and the span category is registered."""
    from flashinfer_tpu.obs import catalog, spans

    for name in ("steploop.steps", "steploop.idle_ticks",
                 "steploop.host_us", "steploop.phase_us",
                 "steploop.device_us", "steploop.gap_us",
                 "engine.idle_steps"):
        assert name in catalog.METRICS, name
    assert "steploop.pred_vs_measured" not in catalog.METRICS
    assert not hasattr(catalog, "DRIFT_RATIO_BUCKETS")
    for phase in ("lower.build", "dispatch.call", "fetch", "post",
                  "lower.build.l0", "lower.build.window.l1",
                  "dispatch.upload.pack", ".cut", "gc", "compile.trace"):
        assert phase in catalog.METRICS["steploop.phase_us"][2]
    assert "steploop" in spans.SPAN_CATEGORIES_VALID
