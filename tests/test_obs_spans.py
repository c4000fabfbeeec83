"""Serving flight recorder (ISSUE 10): obs.spans + the lifecycle API.

Pins the tentpole contracts:

- **zero-overhead subprocess pin**: with ``FLASHINFER_TPU_SPANS``
  unset, plain library use (decorated ops, wrapper plan/run, a
  ServingEngine run) never imports the spans machinery at all — the
  costmodel precedent, one notch stronger than branch-counting;
- **ring-buffer bound**: the recorder keeps exactly ``capacity`` spans
  and counts (never silently loses) the overwritten ones;
- **retrace-cause diff**: change ONE frozen static -> exactly that key
  reported, for both the wrapper replan path and the serving steps'
  run-state path;
- **TTFT/TPOT histogram math** against hand-computed values (driven
  with explicit clocks, no wall-time flake);
- the unified chrome-trace export: one clock base for spans and the op
  timeline, schema-valid, and the ``obs trace --selftest`` CLI
  acceptance run.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))


@pytest.fixture()
def spans_on(monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_SPANS", "1")
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import spans

    obs.reset()
    spans.reset()
    yield
    obs.reset()
    spans.reset()


# ------------------------------------------------------- zero overhead --


@pytest.mark.quick
def test_spans_gate_off_is_noop_and_import_free(monkeypatch):
    """Gate off: the facade helpers cost one env check, return inert
    values, and never import obs.spans (in-process form of the
    subprocess pin below)."""
    monkeypatch.delenv("FLASHINFER_TPU_SPANS", raising=False)
    sys.modules.pop("flashinfer_tpu.obs.spans", None)
    from flashinfer_tpu import obs

    assert obs.spans_enabled() is False
    with obs.span("x", cat="host"):
        pass
    assert obs.state_signature((1, 2)) is None
    obs.request_begin("r")
    obs.prefill_chunk("r", 4)
    obs.decode_step("r")
    assert obs.request_finish("r") is None
    assert obs.lifecycle_snapshot() == {}
    obs.record_retrace("W", {"k": (1, 2)})
    assert "flashinfer_tpu.obs.spans" not in sys.modules


def test_zero_overhead_subprocess_pin():
    """THE tentpole pin: a subprocess doing plain library work — a
    decorated op, a decode-wrapper plan, a ServingEngine run — must
    never load flashinfer_tpu.obs.spans (same standard as
    the metrics registry / costmodel zero-overhead pins)."""
    code = """
import sys
import numpy as np
import jax, jax.numpy as jnp
import flashinfer_tpu as fi
fi.rmsnorm(jnp.ones((4, 64), jnp.float32), jnp.ones((64,), jnp.float32))
w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="NHD")
w.plan(np.array([0, 2, 4], np.int32), np.arange(4, dtype=np.int32),
       np.array([4, 4], np.int32), 4, 2, 64, 4)
from flashinfer_tpu.models import LlamaConfig, init_llama_params
from flashinfer_tpu.serve import EngineConfig, EngineRequest, ServingEngine
cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
params = init_llama_params(jax.random.PRNGKey(0), cfg)
eng = ServingEngine(cfg, params, EngineConfig(
    num_pages=16, page_size=8, max_batch=2, prefill_budget_tokens=8,
    max_seq_tokens=16))
eng.submit(EngineRequest("r0", [3, 1, 4], max_new_tokens=3))
eng.submit(EngineRequest("r1", [1, 5, 9, 2, 6], max_new_tokens=3))
assert all(len(t) == 3 for t in eng.run().values())
assert eng.num_traces == 2  # the mixed rung and the decode rung
assert "flashinfer_tpu.obs.spans" not in sys.modules, \\
    "spans machinery loaded on plain library use"
print("SPANS_ZERO_OVERHEAD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("FLASHINFER_TPU_SPANS", "FLASHINFER_TPU_METRICS"):
        env.pop(var, None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "SPANS_ZERO_OVERHEAD_OK" in p.stdout


# ---------------------------------------------------------- ring buffer --


@pytest.mark.quick
def test_ring_buffer_bound_pin(spans_on):
    """The recorder is a RING: capacity is the hard bound, overwrites
    keep the newest window, and the lifetime/dropped counts stay
    exact."""
    from flashinfer_tpu.obs import spans

    spans.reset(capacity=8)
    for i in range(13):
        spans.record_instant(f"e{i}", "host")
    rec = spans.get_recorder()
    kept = spans.drain()
    assert len(kept) == 8 == rec.capacity
    assert [e["name"] for e in kept] == [f"e{i}" for i in range(5, 13)]
    assert rec.total == 13
    assert rec.dropped() == 5


def test_recorder_thread_safety_counts_exact(spans_on):
    from flashinfer_tpu.obs import spans

    spans.reset(capacity=100_000)
    N, K = 8, 500

    def work(t):
        for i in range(K):
            with spans.span(f"outer{t}", cat="host"):
                spans.record_instant(f"inner{t}.{i}", "host")

    threads = [threading.Thread(target=work, args=(t,)) for t in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert spans.get_recorder().total == N * K * 2
    # nesting is per-thread: every inner span parents under an outer
    # span from the SAME thread
    by_id = {s["span_id"]: s for s in spans.drain()}
    inners = [s for s in by_id.values() if s["name"].startswith("inner")]
    assert inners and all(
        by_id[s["parent_id"]]["tid"] == s["tid"] for s in inners)


def test_spans_cap_env_default(spans_on, monkeypatch):
    from flashinfer_tpu.obs import spans

    monkeypatch.setenv("FLASHINFER_TPU_SPANS_CAP", "16")
    spans.reset()
    assert spans.get_recorder().capacity == 16


# ------------------------------------------------- retrace-cause diffs --


@pytest.mark.quick
def test_wrapper_replan_diff_names_exact_static(spans_on):
    """Change ONE frozen plan static between plans -> exactly that key
    in plan.retrace_cause and in the retrace span's diff."""
    import numpy as np

    import flashinfer_tpu as fi
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import spans

    w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="NHD")
    args = (np.array([0, 2, 4], np.int32), np.arange(4, dtype=np.int32),
            np.array([4, 4], np.int32), 4, 2, 64, 4)
    w.plan(*args)
    w.plan(*args, window_left=5)
    cells = obs.snapshot()["counters"]["plan.retrace_cause"]
    assert cells == {
        "{key=window_left,wrapper=BatchDecodeWithPagedKVCacheWrapper}": 1}
    retrace = [s for s in spans.drain() if s["cat"] == "retrace"]
    assert len(retrace) == 1
    assert list(retrace[0]["attrs"]["changed"]) == ["window_left"]
    # an identical replan attributes nothing new
    w.plan(*args, window_left=5)
    assert obs.snapshot()["counters"]["plan.retrace_cause"] == cells


def test_sharded_step_retrace_names_moved_state_leaf(
        spans_on, tiny_sharded_step):
    """A retrace under a live ShardedServingStep plan (one run-state
    static moved: the dtype of the activations handed in) attributes
    to exactly that leaf."""
    import jax.numpy as jnp

    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import spans

    st, x0, layer_ws, head, head_s, state = tiny_sharded_step
    for _ in range(3):
        _, *state = st.run(x0, layer_ws, state[0], head, head_s,
                           *state[1:])
    assert st.num_traces == 1
    assert "plan.retrace_cause" not in obs.snapshot()["counters"]

    st.run(x0.astype(jnp.float32), layer_ws, state[0], head, head_s,
           *state[1:])
    assert st.num_traces == 2
    assert spans.top_retrace_causes(obs.snapshot()) == [
        {"wrapper": "ShardedServingStep", "key": "x0", "count": 1}]


def test_engine_retrace_attributes_params_change(spans_on):
    """The signature covers EVERY jitted argument — a swapped weight
    dtype (params, outside the donated state) on a rung that has
    compiled attributes to the exact params leaf, not
    '<unattributed>'."""
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import obs
    from flashinfer_tpu.models import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    eng = ServingEngine(
        cfg, init_llama_params(jax.random.PRNGKey(0), cfg),
        EngineConfig(num_pages=16, page_size=8, max_batch=2,
                     prefill_budget_tokens=8, max_seq_tokens=16))
    eng.submit(EngineRequest("r0", [1, 5, 9, 2, 6], max_new_tokens=2))
    eng.run()
    assert eng.num_traces == 2  # the mixed rung and the decode rung
    assert "plan.retrace_cause" not in obs.snapshot()["counters"]
    eng.params = dict(eng.params,
                      embed=eng.params["embed"].astype(jnp.bfloat16))
    eng.submit(EngineRequest("r1", [1, 5, 9, 2, 6], max_new_tokens=1))
    eng.run()
    assert eng.num_traces == 3
    causes = obs.snapshot()["counters"]["plan.retrace_cause"]
    assert list(causes) == [
        "{key=params['embed'],wrapper=ServingEngine}"]


def test_plan_signature_fingerprints_small_arrays():
    """Plan signatures tell VALUE changes of small closed arrays apart
    (an HLO-embedded constant retraces on a value change too); run-state
    signatures deliberately do not."""
    import numpy as np

    from flashinfer_tpu.obs import spans

    a = {"table": np.arange(8, dtype=np.int32), "k": 1}
    b = {"table": np.arange(8, dtype=np.int32)[::-1].copy(), "k": 1}
    changed = spans.diff_statics(spans.plan_signature(a),
                                 spans.plan_signature(b))
    assert list(changed) == ["table"]
    # same values -> no diff
    assert spans.diff_statics(spans.plan_signature(a),
                              spans.plan_signature(dict(a))) == {}
    # state signature: shape/dtype only — same-shape value change is
    # invisible (no device transfer, ever)
    assert spans.state_signature(a) == spans.state_signature(b)


def test_diff_without_prior_signature_is_explicit():
    from flashinfer_tpu.obs import spans

    changed = spans.diff_statics(None, {"x": "1"})
    assert list(changed) == ["<unattributed: no prior signature>"]


# --------------------------------------------------- lifecycle math pin --


@pytest.mark.quick
def test_ttft_tpot_histogram_math_vs_hand_computed(spans_on):
    """Drive the lifecycle with explicit clocks; every histogram value
    must match the hand-computed TTFT/TPOT/queue/tok-s numbers."""
    from flashinfer_tpu import obs

    # request r1: enqueued at t=1.0 (0.5 s before admission), first
    # prefill work at 2.0, tokens at 3.0, 3.25, 3.75, finish at 3.75
    obs.request_begin("r1", enqueue_t=1.0, now=1.5)
    obs.prefill_chunk("r1", 7, now=2.0)
    obs.decode_step("r1", now=3.0)
    obs.decode_step("r1", now=3.25)
    obs.decode_step("r1", num_tokens=2, now=3.75)
    s = obs.request_finish("r1", now=3.75)
    assert s["tokens"] == 4 and s["prefill_tokens"] == 7
    assert s["queue_us"] == pytest.approx(1.0e6)   # 2.0 - 1.0
    assert s["ttft_us"] == pytest.approx(2.0e6)    # 3.0 - 1.0
    assert s["tokens_per_s"] == pytest.approx(4 / 2.75)  # 4 / (3.75-1.0)

    ls = obs.lifecycle_snapshot()
    ttft = ls["lifecycle.ttft_us"]
    assert ttft["count"] == 1 and ttft["sum"] == pytest.approx(2.0e6)
    # TPOT gaps: (3.25-3.0)=0.25 s and (3.75-3.25)/2 = 0.25 s/token
    tpot = ls["lifecycle.tpot_us"]
    assert tpot["count"] == 2
    assert tpot["sum"] == pytest.approx(0.5e6)
    assert tpot["min"] == pytest.approx(0.25e6)
    assert tpot["max"] == pytest.approx(0.25e6)
    queue = ls["lifecycle.queue_us"]
    assert queue["count"] == 1 and queue["sum"] == pytest.approx(1.0e6)
    toks = ls["lifecycle.tokens_per_s"]
    assert toks["count"] == 1 and toks["sum"] == pytest.approx(4 / 2.75)


def test_decode_only_request_closes_queue_at_first_token(spans_on):
    from flashinfer_tpu import obs

    obs.request_begin("d1", now=10.0)
    obs.decode_step("d1", now=10.5)
    s = obs.request_finish("d1", now=10.5)
    assert s["ttft_us"] == pytest.approx(0.5e6)
    assert s["queue_us"] == pytest.approx(0.5e6)
    ls = obs.lifecycle_snapshot()
    # the HISTOGRAM agrees with the summary: first token == first work
    # for a decode-only request, so queue = first token - enqueue
    assert ls["lifecycle.queue_us"]["sum"] == pytest.approx(0.5e6)
    assert "lifecycle.tpot_us" not in ls  # one token: no gap yet


def test_explicit_lifecycle_buckets_declared(spans_on):
    """The catalog pins the TTFT/TPOT boundaries (the satellite's
    'explicit bucket boundaries' requirement) — observations land in
    those buckets, not the µs defaults."""
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs.catalog import (METRICS, TPOT_BUCKETS_US,
                                            TTFT_BUCKETS_US)

    for name in ("lifecycle.queue_us", "lifecycle.ttft_us",
                 "lifecycle.tpot_us", "lifecycle.tokens_per_s",
                 "plan.retrace_cause"):
        assert name in METRICS
    assert TTFT_BUCKETS_US[0] == 1e3 and TTFT_BUCKETS_US[-1] == 6e7
    assert TPOT_BUCKETS_US[0] == 100.0
    obs.request_begin("b1", now=0.0)
    obs.decode_step("b1", now=0.0015)  # 1500 us TTFT
    obs.request_finish("b1", now=0.0015)
    h = obs.lifecycle_snapshot()["lifecycle.ttft_us"]
    assert "2000.0" in h["buckets"]  # the (1e3, 2e3] TTFT bucket


# ------------------------------------------- unified trace + one clock --


@pytest.mark.quick
def test_unified_trace_shares_one_clock_base(spans_on):
    """A profiler op event and a flight-recorder span stamped at the
    SAME perf_counter instant must export at the SAME unified-trace ts
    (the epoch-vs-perf_counter skew fix)."""
    from flashinfer_tpu import obs, profiler
    from flashinfer_tpu.obs import export, spans

    profiler.start_timeline()
    t0 = 100.0
    profiler.record_event("op_x", t0, t0 + 0.001)
    spans.record("span_x", "dispatch", t0, t0 + 0.001)
    events = profiler.stop_timeline()
    trace = export.to_unified_chrome_trace(obs.snapshot(), events,
                                           spans.drain())
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert by_name["op_x"]["ts"] == by_name["span_x"]["ts"]
    assert by_name["op_x"]["ts"] == profiler.perf_to_epoch_us(t0)
    assert by_name["op_x"]["dur"] == pytest.approx(1000.0)
    assert export.validate_chrome_trace(trace) == []


def test_timeline_file_uses_shared_clock_base(tmp_path):
    """profiler.stop_timeline(path)'s standalone file form shares the
    epoch base too — the two previously-disjoint trace files now merge
    on one timeline."""
    from flashinfer_tpu import profiler

    profiler.start_timeline()
    profiler.record_event("y", 5.0, 6.0)
    path = str(tmp_path / "t.json")
    profiler.stop_timeline(path)
    trace = json.loads(open(path).read())
    assert trace["traceEvents"][0]["ts"] == profiler.perf_to_epoch_us(5.0)


def test_validate_chrome_trace_catches_violations():
    from flashinfer_tpu.obs import export

    assert export.validate_chrome_trace({}) \
        == ["trace is not a dict with a traceEvents list"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "pid": 1, "tid": 1,
         "dur": -1.0},
        {"name": "b", "ph": "??", "ts": 0.0},
    ]}
    probs = export.validate_chrome_trace(bad)
    assert any("dur" in p for p in probs)
    assert any("bad ph" in p for p in probs)
    assert any("snapshot" in p for p in probs)
    good = {"traceEvents": [
        {"name": "flashinfer_tpu.obs.snapshot", "ph": "M", "pid": 1,
         "tid": 0, "args": {"snapshot": {"histograms": {}}}}]}
    assert export.validate_chrome_trace(good) == []
    probs = export.validate_chrome_trace(good, require_lifecycle=True)
    assert any("request" in p for p in probs)
    assert any("lifecycle.ttft_us" in p for p in probs)


def test_api_dispatch_spans_nest_under_request(spans_on):
    """@flashinfer_api ops called inside an open lifecycle span parent
    under it — the unified trace nests ops inside requests."""
    import jax.numpy as jnp

    import flashinfer_tpu as fi
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import spans

    with obs.span("request.phase", cat="request"):
        fi.rmsnorm(jnp.ones((4, 64), jnp.float32),
                   jnp.ones((64,), jnp.float32))
    recorded = spans.drain()
    parent = next(s for s in recorded if s["name"] == "request.phase")
    op = next(s for s in recorded if s["name"] == "rmsnorm")
    assert op["parent_id"] == parent["span_id"]
    assert op["cat"] == "dispatch"


# ----------------------------------------------- coverage + CLI surface --


@pytest.mark.quick
def test_serving_ops_span_coverage_closed():
    """catalog.SERVING_OPS x spans.SPAN_CATEGORIES: the doctor's
    unspanned list must be empty (L005 extended to spans), and every
    declared category is a valid one."""
    from flashinfer_tpu.obs import spans
    from flashinfer_tpu.obs.catalog import API_OPS, SERVING_OPS

    assert SERVING_OPS <= API_OPS
    assert SERVING_OPS - set(spans.SPAN_CATEGORIES) == frozenset()
    assert set(spans.SPAN_CATEGORIES.values()) \
        <= spans.SPAN_CATEGORIES_VALID


def test_obs_trace_cli_selftest_acceptance(tmp_path):
    """THE acceptance criterion: `python -m flashinfer_tpu.obs trace
    --selftest` produces a schema-valid unified chrome trace with
    request-lifecycle spans, lifecycle histograms in the embedded
    snapshot, a held retrace budget over the engine's loop, and the
    deliberately perturbed static named in the retrace-cause table."""
    out = str(tmp_path / "unified.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLASHINFER_TPU_SPANS", None)
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "trace",
         "--selftest", "--steps", "9", "--out", out],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=560,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    summary = json.loads(p.stdout)
    assert summary["problems"] == []
    assert summary["num_traces_loop"] == summary["rungs"] == 2
    assert {"wrapper": "ServingEngine", "key": "params['embed']",
            "count": 1} in summary["retrace_causes"]
    trace = json.loads(open(out).read())
    cats = {e.get("cat") for e in trace["traceEvents"]}
    assert {"request", "decode", "dispatch", "retrace"} <= cats
    snap_ev = next(e for e in trace["traceEvents"]
                   if e["name"] == "flashinfer_tpu.obs.snapshot")
    hists = snap_ev["args"]["snapshot"]["histograms"]
    assert "lifecycle.ttft_us" in hists and "lifecycle.tpot_us" in hists


def test_doctor_reports_spans_and_retrace_causes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "doctor"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    report = json.loads(p.stdout)
    assert report["spans"]["unspanned_serving_ops"] == []
    assert set(report["spans"]["serving_ops"]) == {
        "parallel.sharded_step", "engine.step",
        # the tiered-KV movements (serve/kv_tier.py, ISSUE 13)
        "engine.kv_spill", "engine.kv_restore", "engine.kv_migrate"}
    assert report["retrace_causes"] == []  # fresh process: nothing hot
    assert "FLASHINFER_TPU_SPANS" in report["flags"]
