"""flashinfer_tpu.obs — unified runtime observability (ISSUE 2).

Covers the metrics registry + exporters, the ``@flashinfer_api``
instrumentation (including the two satellite regression tests: the
zero-overhead fast path and the trace-apply/timeline interaction), the
plan-lifecycle wiring, profiler thread-safety, the bench row-quality
auditor, and the ``python -m flashinfer_tpu.obs report`` acceptance
criterion (per-op counters + plan-lifecycle metrics after a
tier-1-sized run).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from flashinfer_tpu import obs
from flashinfer_tpu.obs import bench_audit, export
from flashinfer_tpu.obs.registry import Histogram, Registry

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))


@pytest.fixture()
def metrics_on(monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    obs.reset()
    yield
    obs.reset()


@pytest.fixture()
def all_obs_off(monkeypatch):
    for var in ("FLASHINFER_TPU_METRICS", "FLASHINFER_TPU_LOGLEVEL",
                "FLASHINFER_TPU_TRACE_DUMP", "FLASHINFER_TPU_TRACE_APPLY"):
        monkeypatch.delenv(var, raising=False)


# ---------------------------------------------------------------- registry --


@pytest.mark.quick
def test_registry_counter_gauge_histogram(metrics_on):
    reg = Registry()
    assert reg.counter_inc("c", op="a") == 1
    assert reg.counter_inc("c", 2, op="a") == 3
    assert reg.counter_inc("c", op="b") == 1
    reg.gauge_set("g", 4.5)
    for v in (5, 15, 150, 1500):
        reg.observe("h", v, op="a")
    snap = reg.snapshot()
    assert snap["counters"]["c"]["{op=a}"] == 3
    assert snap["counters"]["c"]["{op=b}"] == 1
    assert snap["gauges"]["g"][""] == 4.5
    h = snap["histograms"]["h"]["{op=a}"]
    assert h["count"] == 4 and h["min"] == 5 and h["max"] == 1500
    assert 5 <= h["p50"] <= 150  # interpolated, clamped to [min, max]
    assert h["p99"] <= 1500


def test_histogram_quantiles_clamped_and_monotone():
    h = Histogram((1.0, 10.0, 100.0))
    for v in (2, 3, 4, 50):
        h.observe(v)
    q50, q90, q99 = h.quantile(0.5), h.quantile(0.9), h.quantile(0.99)
    assert 2 <= q50 <= 50 and q50 <= q90 <= q99 <= 50
    assert Histogram((1.0,)).quantile(0.5) is None  # empty


def test_registry_thread_safety_counts_exact():
    reg = Registry()
    N, K = 8, 500

    def work():
        for _ in range(K):
            reg.counter_inc("c")
            reg.observe("h", 1.0)

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["c"][""] == N * K
    assert snap["histograms"]["h"][""]["count"] == N * K


def test_gate_off_is_noop(monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "0")
    obs.reset()
    assert obs.counter_inc("c") == 0
    obs.observe("h", 1.0)
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


# --------------------------------------------------------------- exporters --


def _sample_snapshot():
    reg = Registry()
    reg.counter_inc("api.calls", 3, op="rmsnorm")
    reg.observe("api.dispatch_us", 42.0, op="rmsnorm")
    reg.gauge_set("g", 1.0)
    return reg.snapshot()


def test_prometheus_format():
    text = export.to_prometheus(_sample_snapshot())
    assert 'flashinfer_tpu_api_calls_total{op="rmsnorm"} 3' in text
    assert "# TYPE flashinfer_tpu_api_dispatch_us histogram" in text
    assert 'le="+Inf"' in text
    assert 'flashinfer_tpu_api_dispatch_us_count{op="rmsnorm"} 1' in text
    assert "# HELP flashinfer_tpu_api_calls" in text  # catalog help wired


def test_chrome_trace_merges_timeline_and_snapshot():
    events = [{"name": "rmsnorm", "ts": 1.0, "dur": 0.001}]
    trace = export.to_chrome_trace(_sample_snapshot(), events)
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    metas = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert spans[0]["name"] == "rmsnorm" and spans[0]["dur"] == 1000.0
    assert metas and "api.calls" in \
        metas[0]["args"]["snapshot"]["counters"]


# ------------------------------------------------- @flashinfer_api metrics --


def test_api_decorator_records_per_op_metrics(metrics_on):
    from flashinfer_tpu.api_logging import flashinfer_api

    @flashinfer_api(name="obs_unit_op")
    def op(x):
        return x * 2

    for i in range(3):
        assert op(i) == 2 * i
    snap = obs.snapshot()
    assert snap["counters"]["api.calls"]["{op=obs_unit_op}"] == 3
    assert snap["counters"]["api.calls_total"][""] == 3
    assert snap["histograms"]["api.dispatch_us"]["{op=obs_unit_op}"][
        "count"] == 3
    assert op.__flashinfer_api_name__ == "obs_unit_op"


def test_zero_overhead_fast_path(all_obs_off):
    """Satellite: with metrics, logging, trace, and timeline ALL
    disabled, a decorated op hits the SINGLE fast-path branch — one
    `_instrumentation_active` check, then the plain call; the slow path
    must not run (asserted via call-count on stubs, not wall-clock), so
    the disabled path can never quietly grow per-call work."""
    from flashinfer_tpu import api_logging, profiler

    assert not profiler.timeline_active()
    assert api_logging._instrumentation_active() is False

    checks = []
    monkey_active = lambda: (checks.append(1), False)[1]
    bomb = lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("slow path ran with all surfaces disabled"))
    orig_active = api_logging._instrumentation_active
    orig_slow = api_logging._instrumented_call
    api_logging._instrumentation_active = monkey_active
    api_logging._instrumented_call = bomb
    try:
        inner = []

        @api_logging.flashinfer_api
        def op(x):
            inner.append(x)
            return x + 1

        assert op(41) == 42
        assert op(1) == 2
    finally:
        api_logging._instrumentation_active = orig_active
        api_logging._instrumented_call = orig_slow
    assert inner == [41, 1]
    assert len(checks) == 2  # exactly one branch check per call


def test_trace_apply_with_timeline_records_substituted_span(
        monkeypatch, metrics_on):
    """Satellite: with FLASHINFER_TPU_TRACE_APPLY=1 AND an active
    timeline, the recorded span covers the SUBSTITUTED solution — the
    'profiled run executes the SAME configuration' contract in
    api_logging was previously untested."""
    from flashinfer_tpu import profiler, trace
    from flashinfer_tpu.api_logging import flashinfer_api

    monkeypatch.setenv("FLASHINFER_TPU_TRACE_APPLY", "1")
    trace.clear_solutions()

    @flashinfer_api(name="obs_sub_op")
    def op(x, mode="a"):
        return ("default", x)

    def sub(x, mode="a"):
        time.sleep(0.005)
        return ("substituted", x)

    trace.register_solution("obs_sub_op", {"mode": "b"}, sub)
    profiler.start_timeline()
    try:
        out_sub = op(1, mode="b")
        out_def = op(1, mode="a")
    finally:
        events = profiler.stop_timeline()
        trace.clear_solutions()
    assert out_sub == ("substituted", 1)
    assert out_def == ("default", 1)
    spans = [e for e in events if e["name"] == "obs_sub_op"]
    assert len(spans) == 2
    # the first span wraps the substitute, so its duration must cover
    # the substitute's 5 ms sleep
    assert spans[0]["dur"] >= 0.004
    snap = obs.snapshot()
    assert snap["counters"]["trace.solution_hits"]["{op=obs_sub_op}"] == 1
    assert snap["counters"]["trace.solution_misses"]["{op=obs_sub_op}"] == 1


def test_traced_api_counts_hits_and_misses(monkeypatch, metrics_on):
    from flashinfer_tpu import trace

    monkeypatch.setenv("FLASHINFER_TPU_TRACE_APPLY", "1")
    trace.clear_solutions()

    @trace.traced_api(name="obs_traced_op")
    def op(x):
        return x

    trace.register_solution("obs_traced_op", {"arg0": 7}, lambda x: -x)
    assert op(7) == -7
    assert op(8) == 8
    trace.clear_solutions()
    snap = obs.snapshot()
    assert snap["counters"]["trace.solution_hits"]["{op=obs_traced_op}"] == 1
    assert snap["counters"]["trace.solution_misses"][
        "{op=obs_traced_op}"] == 1


# ------------------------------------------------- plan lifecycle metrics --


def test_decode_plan_lifecycle_metrics(metrics_on):
    import numpy as np

    import flashinfer_tpu as fi

    w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="NHD")
    indptr = np.array([0, 2, 4], np.int32)
    indices = np.arange(4, dtype=np.int32)
    last = np.array([4, 4], np.int32)
    w.plan(indptr, indices, last, 4, 2, 64, 4)
    w.plan(indptr, indices, last, 4, 2, 64, 4)  # re-plan
    snap = obs.snapshot()
    name = "BatchDecodeWithPagedKVCacheWrapper"
    assert snap["counters"]["plan.calls"]["{wrapper=%s}" % name] == 2
    assert snap["counters"]["plan.replans"]["{wrapper=%s}" % name] == 1
    waste = snap["histograms"]["plan.padding_waste_pct"]
    # batch 2 pads to 8 (75% waste), 4 pages pad to 8x8=64 slots
    batch_h = waste["{axis=batch,wrapper=%s}" % name]
    assert batch_h["count"] == 2 and abs(batch_h["max"] - 75.0) < 1e-6
    pages_h = waste["{axis=pages,wrapper=%s}" % name]
    assert abs(pages_h["max"] - 100.0 * (1 - 4 / 64)) < 1e-6


def test_prefill_plan_and_sm_scale_rebind_metrics(metrics_on):
    import numpy as np

    import flashinfer_tpu as fi

    w = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="NHD")
    w.plan(np.array([0, 2, 4], np.int32), np.array([0, 2, 4], np.int32),
           np.arange(4, dtype=np.int32), np.array([4, 4], np.int32),
           4, 2, 64, 4, causal=True)
    restore = w._rebind_sm_scale(absolute=0.5)
    assert restore is not None
    w._plan = restore
    snap = obs.snapshot()
    name = "BatchPrefillWithPagedKVCacheWrapper"
    assert snap["counters"]["plan.calls"]["{wrapper=%s}" % name] == 1
    assert snap["counters"]["plan.sm_scale_rebinds"][
        "{wrapper=%s}" % name] == 1
    waste = snap["histograms"]["plan.padding_waste_pct"]
    # 4 q tokens pad to 128
    q_h = waste["{axis=q_tokens,wrapper=%s}" % name]
    assert abs(q_h["max"] - 100.0 * (1 - 4 / 128)) < 1e-6


# ------------------------------------------------ profiler thread-safety --


def test_profiler_concurrent_record_and_stop():
    """Satellite: record_event/stop_timeline share a lock — a stop
    mid-stream must neither crash a concurrent recorder nor let a
    second stop double-export."""
    from flashinfer_tpu import profiler

    profiler.start_timeline()
    stop_events = []
    errors = []

    def recorder():
        try:
            for i in range(2000):
                profiler.record_event("op", float(i), float(i) + 0.5)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def stopper():
        time.sleep(0.002)
        stop_events.append(profiler.stop_timeline())

    threads = [threading.Thread(target=recorder) for _ in range(4)]
    ts = threading.Thread(target=stopper)
    for t in threads:
        t.start()
    ts.start()
    for t in threads + [ts]:
        t.join()
    assert errors == []
    assert profiler.stop_timeline() == []  # concurrent-stop guard
    assert not profiler.timeline_active()
    assert all(e["dur"] == 0.5 for e in stop_events[0])


def test_timeline_stop_twice_returns_events_once(tmp_path):
    from flashinfer_tpu import profiler

    profiler.start_timeline()
    profiler.record_event("x", 0.0, 1.0)
    path = str(tmp_path / "t.json")
    events = profiler.stop_timeline(path)
    assert len(events) == 1
    assert profiler.stop_timeline() == []
    trace = json.loads(open(path).read())
    assert trace["traceEvents"][0]["name"] == "x"


# ------------------------------------------------------- bench row audit --


def _row(tbps, **cfg):
    return dict(phase="decode", bs=64, ctx=4096, tbps=tbps, **cfg)


def test_row_auditor_quality_rules():
    a = bench_audit.RowAuditor([_row(0.73)])
    ok = a.stamp(_row(0.70))
    assert ok["quality"] == "ok" and ok["vs_best"] == round(0.70 / 0.73, 3)
    assert a.stamp(_row(0.40))["quality"] == "degraded"
    # the committed <0.35x rule (the 2026-07-31 19x artifact shape)
    assert a.stamp(_row(0.0378))["quality"] == "poison"
    # a different configuration never competes with this one
    other = a.stamp(dict(phase="decode", bs=1, ctx=512, tbps=0.01))
    assert other["quality"] == "ok" and "vs_best" not in other


def test_row_auditor_poison_history_does_not_set_baseline():
    poisoned = _row(10.0)
    poisoned["quality"] = "poison"
    a = bench_audit.RowAuditor([poisoned, _row(0.73)])
    assert a.stamp(_row(0.70))["quality"] == "ok"  # best is 0.73, not 10


def test_row_auditor_latency_only_rows_use_inverse_us():
    a = bench_audit.RowAuditor([])
    a.stamp(dict(phase="topk", backend="xla", k=40, us=1000.0))
    slow = a.stamp(dict(phase="topk", backend="xla", k=40, us=5000.0))
    assert slow["quality"] == "poison"  # 5x slower < 0.35x inverse


def test_row_auditor_never_raises_on_garbage():
    a = bench_audit.RowAuditor([])
    row = {"phase": "x", "tbps": float("nan")}
    a.stamp(row)  # must not raise; stamp may be absent or ok
    assert a.stamp({"phase": "y", "weird": object()}) is not None


def test_load_banked_history_parses_run_records(tmp_path):
    # the banked-file format: prose around ```json run records, each
    # with a "rows" list (bench.py --bank writes it)
    bank = tmp_path / "BANK.md"
    bank.write_text(
        "## run 1\n```json\n" + json.dumps({"rows": [
            dict(phase="decode", bs=64, ctx=4096, us=1443.0, tbps=0.7448),
            dict(phase="mla", bs=64, ctx=4096, us=1328.2, tbps=0.2526),
        ]}) + "\n```\nnotes\n```json\n" + json.dumps({"rows": [
            dict(phase="decode", bs=1, ctx=512, us=30.0, tbps=0.27),
        ]}) + "\n```\n")
    rows = bench_audit.load_banked_history(str(bank))
    assert len(rows) == 3
    assert any(r.get("phase") == "decode" for r in rows)
    assert bench_audit.load_banked_history("/nonexistent") == []


# ------------------------------------------------------------- moe drops --


def test_record_dropped_tokens_eager_and_tracer(metrics_on):
    import jax
    import jax.numpy as jnp

    obs.record_dropped_tokens(jnp.array([3], jnp.int32), "alltoall")
    # tracers are skipped, not crashed on
    jax.jit(lambda d: obs.record_dropped_tokens(d, "alltoall") or d)(
        jnp.array([5], jnp.int32))
    snap = obs.snapshot()
    assert snap["counters"]["moe.dropped_tokens"]["{dispatch=alltoall}"] == 3

    from flashinfer_tpu import moe_ep

    assert moe_ep.record_dropped_tokens(
        jnp.array([2], jnp.int32), moe_ep.EpAlgorithm.ALLTOALL) == 2
    assert snap != obs.snapshot()


# ------------------------------------------------------------------- CLI --


def test_obs_report_cli_acceptance():
    """THE acceptance criterion: `python -m flashinfer_tpu.obs report`
    emits a JSON snapshot containing per-op counters and plan-lifecycle
    metrics after a tier-1-sized run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLASHINFER_TPU_METRICS", None)
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "report"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    snap = json.loads(p.stdout)
    ops = {k.strip("{}").partition("=")[2]
           for k in snap["counters"]["api.calls"]}
    assert {"rmsnorm", "silu_and_mul", "sampling_from_probs",
            "single_prefill_with_kv_cache"} <= ops
    assert snap["counters"]["plan.calls"]
    assert any(v >= 1 for v in snap["counters"]["plan.replans"].values())
    assert "plan.padding_waste_pct" in snap["histograms"]
    assert "api.dispatch_us" in snap["histograms"]


def test_obs_doctor_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "doctor"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    report = json.loads(p.stdout)
    assert {"env", "flags", "quarantine", "registry", "lint"} \
        <= set(report)
    assert report["env"].get("flashinfer_tpu")
    assert "FLASHINFER_TPU_METRICS" in report["flags"]
    # lint hygiene: reasonless suppressions are L000/W000 — the tree
    # cannot pass the analyzer with a non-zero count, so doctor must
    # report zero here (and a total, so drift is visible)
    assert report["lint"]["reasonless_suppressions"] == 0
    assert report["lint"]["suppressions"] >= 1
    # cost-model coverage (ISSUE 5): a decorated public op with no
    # roofline formula would bench but never attribute — the doctor
    # must report the straggler list, and the tree must keep it empty
    assert report["costmodel"]["uncovered_api_ops"] == []
    assert report["costmodel"]["api_ops_covered"] >= 10
    assert report["costmodel"]["chip"] in ("v4", "v5e", "v5p", "v6e")


# ------------------------------------------------------------ doc parity --


def test_observability_doc_names_every_catalog_metric():
    from flashinfer_tpu.obs.catalog import API_OPS, METRICS

    doc = open(os.path.join(REPO_ROOT, "docs", "observability.md")).read()
    for name in METRICS:
        assert f"`{name}`" in doc or name in doc, \
            f"docs/observability.md missing metric {name}"
    # and the doc is linked from README + migration guide
    assert "docs/observability.md" in open(
        os.path.join(REPO_ROOT, "README.md")).read()
    assert "observability.md" in open(
        os.path.join(REPO_ROOT, "docs", "migration.md")).read()
    assert API_OPS  # non-empty catalog backs L005
