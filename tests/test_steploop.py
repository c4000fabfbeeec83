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
the live ledger join).
"""

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
    steploop.reset(capacity=64)
    yield
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
print("PIN_OK")
"""


def test_zero_overhead_subprocess_pin():
    """THE zero-overhead pin: a plain gate-off serving loop (the
    wired ServingEngine surface) must finish without ``obs.steploop``
    ever entering sys.modules — the facade checks the gate BEFORE the
    import, so disabled processes pay nothing, not even module init."""
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
    # the mark protocol books host phases only: nothing in ``spans``
    assert r2["spans"] == {} and r2["phases"] == {
        "dispatch": pytest.approx(0.1)}
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
    # children, fetch and post are booked beside the five, per step
    for r in recs:
        assert {"dispatch.upload", "dispatch.call", "fetch", "post"} \
            <= set(r["spans"])
        assert r["spans"]["dispatch.upload"] + r["spans"]["dispatch.call"] \
            <= r["phases"]["dispatch"]
        # fetch opens a few microseconds after the dispatch returned
        assert r["spans"]["fetch"] * 1e6 == pytest.approx(r["device_us"],
                                                          abs=1e3)
    # the record carries the engine's step index, and says which steps
    # traced (compiled): each rung's first, and no other
    assert [r["attrs"]["step"] for r in recs] == list(range(len(recs)))
    traced = [r for r in recs if r["attrs"].get("traced")]
    assert len(traced) == eng.num_traces \
        == len({r["attrs"]["rung"] for r in recs})
    snap = obs.snapshot()
    assert sum(snap["counters"]["engine.idle_steps"].values()) == 1


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
             "dispatch.upload", "dispatch.call", "fetch", "post")
    for _, lo, hi, _ in steps:
        inside = {e[0] for e in events if lo <= e[1] and e[2] <= hi}
        assert {f"engine.{n}" for n in names} <= inside
    # a child lies inside its parent
    call = sorted(e for e in events if e[0] == "engine.dispatch.call")
    disp = sorted(e for e in events if e[0] == "engine.dispatch")
    assert all(d[1] <= c[1] and c[2] <= d[2] for c, d in zip(call, disp))


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
    for phase in ("lower.build", "dispatch.call", "fetch", "post"):
        assert phase in catalog.METRICS["steploop.phase_us"][2]
    assert "steploop" in spans.SPAN_CATEGORIES_VALID
