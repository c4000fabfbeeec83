"""Trace capture / apply + profiler + namespace tests."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_trace_dump(monkeypatch, tmp_path):
    from flashinfer_tpu.trace import traced_api

    monkeypatch.setenv("FLASHINFER_TPU_TRACE_DUMP", "1")
    monkeypatch.setenv("FLASHINFER_TPU_DUMP_DIR", str(tmp_path))

    @traced_api(name="my_op")
    def op(x, k=3):
        return x * k

    op(jnp.ones((2, 4)), k=5)
    lines = (tmp_path / "trace.jsonl").read_text().strip().splitlines()
    rec = json.loads(lines[-1])
    assert rec["op"] == "my_op"
    assert rec["axes"]["arg0"] == {"shape": [2, 4], "dtype": "float32"}
    assert rec["axes"]["k"] == 5


def test_trace_apply_substitution(monkeypatch):
    from flashinfer_tpu import trace

    monkeypatch.setenv("FLASHINFER_TPU_TRACE_APPLY", "1")
    trace.clear_solutions()

    @trace.traced_api(name="sub_op")
    def op(x, mode="a"):
        return x + 1

    # solution only for mode="b"
    trace.register_solution("sub_op", {"mode": "b"}, lambda x, mode="b": x + 100)
    np.testing.assert_allclose(np.asarray(op(jnp.zeros(2))), 1)
    np.testing.assert_allclose(np.asarray(op(jnp.zeros(2), mode="b")), 100)
    trace.clear_solutions()
    np.testing.assert_allclose(np.asarray(op(jnp.zeros(2), mode="b")), 1)


def test_trace_disabled_zero_overhead(monkeypatch):
    from flashinfer_tpu.trace import traced_api

    monkeypatch.delenv("FLASHINFER_TPU_TRACE_DUMP", raising=False)
    monkeypatch.delenv("FLASHINFER_TPU_TRACE_APPLY", raising=False)
    calls = []

    @traced_api(name="plain")
    def op(x):
        calls.append(1)
        return x

    op(jnp.ones(1))
    assert calls == [1]


def test_profiler_annotate_runs():
    from flashinfer_tpu.profiler import annotate

    with annotate("test_span"):
        out = jnp.sum(jnp.ones((8, 8)))
    assert float(out) == 64.0


def test_namespaces():
    from flashinfer_tpu import dsv3_ops, diffusion_ops

    assert hasattr(dsv3_ops, "BatchMLAPagedAttentionWrapper")
    assert hasattr(dsv3_ops, "route_deepseek_v3")
    out = dsv3_ops.router_gemm(jnp.ones((4, 8)), jnp.ones((8, 16)))
    assert out.shape == (4, 16)
    assert hasattr(diffusion_ops, "layernorm_scale_shift")


def test_in_kernel_event_trace_fused_prefill(tmp_path):
    """Device-side event tags from the fused prefill kernel decode to the
    grid schedule and export to a perfetto-compatible trace (reference
    profiler.cuh device tag buffer, TPU sequential-grid form)."""
    import numpy as np

    from flashinfer_tpu import profiler
    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_work_units, fused_paged_prefill,
    )

    PS, HQ, HKV, D = 8, 4, 2, 32
    qo_indptr = np.array([0, 40])
    kv_lens = np.array([64], np.int64)
    kv_page_indptr = np.array([0, 8])
    kv_indices = np.arange(8, dtype=np.int32)
    plan_np = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_indices, kv_lens,
        block_q=64, pages_per_chunk=4, page_size=PS,
    )
    num_units = plan_np.pop("num_units")
    plan_np.pop("block_q"), plan_np.pop("pages_per_chunk")
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    q = jax.random.normal(jax.random.PRNGKey(0), (40, HQ, D), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(1), (8, HKV, PS, D))
    vc = jax.random.normal(jax.random.PRNGKey(2), (8, HKV, PS, D))
    out, events = fused_paged_prefill(
        q, kc, vc, plan, num_units=num_units, block_q=64,
        pages_per_chunk=4, trace_events=True,
    )
    assert out.shape == (40, HQ, D)
    ev = np.asarray(events)
    assert ev.shape == (HKV, num_units)
    # tags decode to the exact grid schedule
    # one tag per unit the launch ran (the plan's real units, which lead
    # the arrays), none for the pad units past its trip count
    n_units = int(plan["n_units"])
    assert 0 < n_units < num_units and not ev[:, n_units:].any()
    for h in range(HKV):
        for u in range(n_units):
            blk, grp, ei, et, sm = profiler.decode_tag(
                int(ev[h, u]), num_units, 1
            )
            assert (sm, blk, et) == (h, u, 2), (h, u, ev[h, u])
    # and the buffer round-trips through the perfetto exporter
    buf = profiler.grid_trace_to_buffer(ev)
    f = tmp_path / "trace.json"
    profiler.export_to_perfetto_trace(buf, ["unit"], str(f))
    import json

    tr = json.load(open(f))["traceEvents"]
    assert len(tr) == HKV * num_units - sum(
        1 for h in range(HKV) for u in range(num_units) if ev[h, u] == 0
    )
