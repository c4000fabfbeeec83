"""Roofline attribution tests (ISSUE 5).

Pins the three halves of the attribution engine:

- ``obs.hwspec`` — the chip-spec registry is the single source of
  truth (VMEM caps, peaks, aliases, env-overridable detection);
- ``obs.costmodel`` — every formula is pinned against a brute-force
  count on tiny shapes (attention/MLA/gmm FLOPs + read/write bytes,
  quantized-KV byte widths, fused-prefill launched-vs-effective from a
  REAL ``build_prefill_work_units`` plan);
- ``obs.roofline`` — attribute/stamp math by hand, the auditor's
  roofline-fraction comparison space, and the ``obs perf`` doctor reproducing the round-5 VERDICT headline
  fractions from BENCH_BANKED.md with a schema-stable JSON form.

Plus the zero-overhead pin: plain library use (metrics off, no bench)
never imports the cost model at all.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flashinfer_tpu.obs import bench_audit, costmodel, hwspec, roofline
from flashinfer_tpu.obs.costmodel import Cost

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir))


# ---------------------------------------------------------------------------
# hwspec: the registry
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_hwspec_registry_single_source_of_truth(monkeypatch):
    # VMEM_CAPS (what analysis L009 imports) is derived from the specs,
    # never a second literal table
    assert hwspec.VMEM_CAPS == {
        name: s.vmem_bytes for name, s in hwspec.CHIP_SPECS.items()}
    assert hwspec.VMEM_CAPS["v5e"] == 64 * 1024 * 1024
    assert hwspec.VMEM_CAPS["v5p"] == 128 * 1024 * 1024

    # lookup: canonical names, aliases, device-kind-ish strings; a chip
    # that is not in the table is an error, never another chip's peaks
    assert hwspec.spec("v5e").hbm_tbps == pytest.approx(0.819)
    assert hwspec.spec("TPU v5 lite").name == "v5e"
    assert hwspec.spec("TPU v5p").name == "v5p"
    assert hwspec.spec("trillium").name == "v6e"
    with pytest.raises(KeyError, match="unknown chip"):
        hwspec.spec("quantum-chip-9000")

    # peak mapping for pre-roofline banked rows (they carry only `peak`)
    assert hwspec.spec_for_peak_tbps(0.819).name == "v5e"
    assert hwspec.spec_for_peak_tbps(2.765).name == "v5p"
    assert hwspec.spec_for_peak_tbps(123.0) is None
    assert hwspec.spec_for_peak_tbps("garbage") is None

    # dtype normalization + ridge point
    v5e = hwspec.spec("v5e")
    assert v5e.peak_tflops("bfloat16") == pytest.approx(197.0)
    assert v5e.peak_tflops("float8_e4m3fn") == pytest.approx(394.0)
    assert v5e.peak_tflops("no_such_dtype") == pytest.approx(197.0)
    assert v5e.ridge_intensity("bf16") == pytest.approx(197.0 / 0.819)

    # detection: env override wins and works with no accelerator
    monkeypatch.setenv("FLASHINFER_TPU_CHIP", "v5p")
    assert hwspec.detect_chip() == "v5p"
    assert hwspec.current_spec().name == "v5p"
    monkeypatch.delenv("FLASHINFER_TPU_CHIP")
    assert hwspec.detect_chip(device_kind="TPU v6e") == "v6e"
    assert hwspec.detect_chip(device_kind="cpu") == hwspec.DEFAULT_CHIP
    with pytest.raises(KeyError, match="unknown chip"):
        hwspec.detect_chip(device_kind="TPU v9 mega")  # a TPU: no default

    # docs table covers every registered chip
    table = hwspec.registry_table()
    assert len(table) == len(hwspec.CHIP_SPECS) + 1


def test_hwspec_import_is_side_effect_free():
    """The lint path (analysis L009) imports hwspec in accelerator-free
    processes: importing it must read no env and touch no backend."""
    src = open(os.path.join(
        REPO_ROOT, "flashinfer_tpu", "obs", "hwspec.py")).read()
    body = src.split('"""', 2)[2]  # strip the module docstring
    for needle in ("os.environ", "jax.devices", "import jax"):
        hits = [ln for ln in body.splitlines()
                if needle in ln and not ln.lstrip().startswith("#")]
        # only inside function bodies (indented), never at module level
        assert all(ln.startswith((" ", "\t")) for ln in hits), needle


# ---------------------------------------------------------------------------
# costmodel: formulas vs brute force on tiny shapes
# ---------------------------------------------------------------------------


def _brute_attention(qo, kv, hq, hkv, dqk, dvo, causal, *, batch=1,
                     qb=2, kvb=2, ob=2, window_left=-1):
    """Independent per-(q, kv)-pair count: 2 FLOPs per madd, QK^T over
    dqk plus PV over dvo; every operand read once, output written once."""
    att = 0
    off = kv - qo
    for qi in range(qo):
        hi = min(qi + off, kv - 1) if causal else kv - 1
        lo = max(qi + off - window_left, 0) if window_left >= 0 else 0
        att += max(hi - lo + 1, 0)
    flops = 2.0 * batch * att * hq * (dqk + dvo)
    bread = batch * (qo * hq * dqk * qb + kv * hkv * (dqk + dvo) * kvb)
    bwrite = batch * qo * hq * dvo * ob
    return flops, float(bread), float(bwrite)


@pytest.mark.quick
@pytest.mark.parametrize("qo,kv,causal", [
    (1, 7, False), (5, 5, True), (3, 11, True), (8, 8, False),
    (7, 16, True), (1, 1, True),
])
def test_costmodel_attention_pinned_against_brute_force(qo, kv, causal):
    hq, hkv, dqk, dvo = 4, 2, 8, 6
    c = costmodel.attention(qo, kv, hq, hkv, dqk, dvo, causal=causal,
                            batch=3)
    f, br, bw = _brute_attention(qo, kv, hq, hkv, dqk, dvo, causal,
                                 batch=3)
    assert c.flops == pytest.approx(f)
    assert c.bytes_read == pytest.approx(br)
    assert c.bytes_written == pytest.approx(bw)
    assert c.flops_effective is None  # plain attention has no waste


@pytest.mark.parametrize("qo,kv,causal,window", [
    (4, 9, True, -1), (4, 9, False, -1), (6, 6, True, 2), (3, 8, False, 4),
])
def test_attended_tokens_matches_dense_mask(qo, kv, causal, window):
    """attended_tokens (the counted term of every attention formula)
    against an explicit dense mask with the bottom-right alignment."""
    off = kv - qo
    mask = np.ones((qo, kv), bool)
    for qi in range(qo):
        for ki in range(kv):
            if causal and ki > qi + off:
                mask[qi, ki] = False
            if window >= 0 and ki < qi + off - window:
                mask[qi, ki] = False
    assert costmodel.attended_tokens(
        qo, kv, causal=causal, window_left=window) == int(mask.sum())


def test_costmodel_quantized_kv_byte_widths():
    """int8/fp8 caches shrink ONLY the kv stream, by exactly the byte
    ratio — the decode win the int8-cache bench measured."""
    bs, ctx, hq, hkv, d = 4, 32, 8, 2, 16
    bf16 = costmodel.paged_decode(bs, ctx, hq, hkv, d, kv_bytes=2)
    int8 = costmodel.paged_decode(bs, ctx, hq, hkv, d, kv_bytes=1)
    kv_stream = bs * ctx * hkv * (d + d)  # tokens x heads x (k+v dims)
    assert bf16.bytes_read - int8.bytes_read == pytest.approx(kv_stream)
    assert bf16.flops == int8.flops  # compute in bf16 either way
    assert bf16.bytes_written == int8.bytes_written
    # decode == single-token attention over the whole cache
    f, br, bw = _brute_attention(1, ctx, hq, hkv, d, d, False,
                                 batch=bs)
    assert bf16.flops == pytest.approx(f)
    assert bf16.bytes_total == pytest.approx(br + bw)


def test_costmodel_mla_decode_brute_force():
    """MLA absorbed decode: latent cache read ONCE for all heads, kpe
    lane-padded to 128 columns (real HBM traffic), FLOPs over the live
    512+64 / 512 dims only."""
    bs, ctx, h, dc, dp = 3, 16, 4, 32, 8
    c = costmodel.mla_decode(bs, ctx, h, latent_dim=dc, rope_dim=dp,
                             lane_pad=16)
    flops = 0.0
    for _ in range(bs):
        for _ in range(ctx):
            for _ in range(h):
                flops += 2 * (dc + dp) + 2 * dc  # q.k then p.v madds
    assert c.flops == pytest.approx(flops)
    # cache streams once per request (NOT per head) at padded width
    assert c.bytes_read == pytest.approx(
        bs * ctx * (dc + 16) * 2 + bs * h * (dc + dp) * 2)
    assert c.bytes_written == pytest.approx(bs * h * dc * 2)
    # the defaults match the DeepSeek layout the bench measures
    d = costmodel.mla_decode(1, 1, 1)
    assert d.flops == pytest.approx(2 * (512 + 64) + 2 * 512)


def test_costmodel_moe_gmm_brute_force():
    tokens, e, h, i, k = 5, 4, 8, 12, 2
    c = costmodel.moe_gmm(tokens, e, h, i, k)
    flops = 0.0
    for _ in range(tokens):
        for _ in range(k):  # each routed choice runs both GEMMs
            flops += 2 * (h * (2 * i)) + 2 * (i * h)
    assert c.flops == pytest.approx(flops)
    # weight traffic: every hot expert streamed once
    hot = min(e, tokens * k)
    assert c.bytes_read >= hot * (h * 2 * i + i * h) * 2
    int8 = costmodel.moe_gmm(tokens, e, h, i, k, weight_bytes=1,
                             dtype="int8")
    assert c.bytes_read - int8.bytes_read == pytest.approx(
        hot * (h * 2 * i + i * h))
    assert int8.dtype == "int8"


def test_costmodel_gemm_norm_rope_sampling_shapes():
    g = costmodel.gemm(3, 5, 7)
    assert g.flops == pytest.approx(2 * 3 * 5 * 7)
    assert g.bytes_read == pytest.approx((3 * 7 + 7 * 5) * 2)
    assert g.bytes_written == pytest.approx(3 * 5 * 2)
    n = costmodel.norm(4, 8)
    assert n.bytes_read == pytest.approx((4 * 8 + 8) * 2)
    r = costmodel.rope(4, 2, 8, quantize_out_bytes=1)
    assert r.bytes_written == pytest.approx(4 * 2 * 8)  # fp8 out width
    s = costmodel.sampling(2, 100)
    assert s.bytes_read == pytest.approx(2 * 100 * 4)  # f32 probs pass
    assert 0 < s.intensity < 1  # bandwidth attribution, not MFU claim


def test_fused_prefill_launched_vs_effective_from_real_plan():
    """Launched/effective work straight from a REAL work-unit plan's
    stats (the PR 3 planner), pinned against brute-force cell counts."""
    from flashinfer_tpu.ops.paged_prefill import build_prefill_work_units

    page, bq, ppc = 2, 4, 2
    qo_lens, kv_lens = [5, 3], [8, 6]
    qo_indptr = np.cumsum([0] + qo_lens).astype(np.int64)
    pages_per = [(kv + page - 1) // page for kv in kv_lens]
    kv_page_indptr = np.cumsum([0] + pages_per).astype(np.int64)
    kv_page_indices = np.arange(kv_page_indptr[-1], dtype=np.int64)

    plan = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(kv_lens, np.int64), bq, ppc, page, causal=False)
    stats = plan["stats"]
    chunk = ppc * page
    # non-causal, nothing prunable: every in-bounds (row, kv-col) cell
    # is useful, so valid cells == the attended-pair count exactly
    assert stats["mxu_cells_valid"] == sum(
        q * kv for q, kv in zip(qo_lens, kv_lens))
    assert stats["mxu_cells_total"] == stats["units"] * bq * chunk
    assert stats["mxu_cells_total"] >= stats["mxu_cells_valid"]

    hq, hkv, d = 4, 2, 8
    c = costmodel.fused_prefill_from_stats(
        stats, block_q=bq, pages_per_chunk=ppc, page_size=page,
        num_qo_heads=hq, num_kv_heads=hkv, head_dim=d,
        total_q=sum(qo_lens))
    per_cell = 2 * hq * (d + d)
    assert c.flops == pytest.approx(stats["mxu_cells_total"] * per_cell)
    assert c.flops_effective == pytest.approx(
        stats["mxu_cells_valid"] * per_cell)
    assert c.flops_effective <= c.flops
    # q streams once per packed tile, kv once per unit chunk
    assert c.bytes_read == pytest.approx(
        stats["tiles"] * bq * hq * d * 2
        + stats["units"] * chunk * hkv * (d + d) * 2)

    # causal pruning: fewer (or equal) launched units, and the wrapper
    # formula reports effective == true attended work, < launched
    causal = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(kv_lens, np.int64), bq, ppc, page, causal=True)
    assert causal["stats"]["units"] <= stats["units"]
    pc = costmodel.paged_prefill(
        1, qo_lens[0], kv_lens[0], hq, hkv, d, causal=True,
        stats=causal["stats"], block_q=bq, pages_per_chunk=ppc,
        page_size=page)
    f_eff, _, _ = _brute_attention(qo_lens[0], kv_lens[0], hq, hkv, d, d,
                                   True)
    assert pc.flops_effective == pytest.approx(f_eff)
    assert pc.flops_effective < pc.flops


def test_serving_step_is_sum_of_phases():
    shape = costmodel.SERVING_SHAPES["llama70b_tp8shard_int8"]
    phases = costmodel.serving_phase_costs(8, 256, 4, **shape)
    assert set(phases) == set(costmodel.SERVING_PHASES)
    full = costmodel.serving_step(8, 256, 4, **shape)
    fitted = costmodel.serving_step(8, 256, 4, include_kv_append=False,
                                    include_sampling=False, **shape)
    total = sum(p.flops for p in phases.values())
    assert full.flops == pytest.approx(total)
    assert fitted.bytes_total == pytest.approx(
        full.bytes_total - phases["kv_append"].bytes_total
        - phases["sampling"].bytes_total)
    assert full.dtype == "int8"  # attributes against the int8 peak


def test_cost_for_bench_row_reconstructs_pre_roofline_rows():
    """Rows banked before cost stamping attribute via the fixed bench
    shapes; stamped rows use their own fields verbatim (and win)."""
    rec = costmodel.cost_for_bench_row(
        {"phase": "decode", "bs": 64, "ctx": 4096, "us": 1000.0})
    assert rec is not None
    cost, seconds = rec
    assert seconds == pytest.approx(1e-3)
    assert cost.flops == costmodel.paged_decode(64, 4096, 32, 8, 128).flops

    stamped = costmodel.cost_for_bench_row(
        {"phase": "decode", "bs": 64, "ctx": 4096, "us": 1000.0,
         "flops": 5.0, "bytes_read": 7.0, "bytes_written": 3.0,
         "flops_effective": 4.0, "dtype": "int8"})
    cost, _ = stamped
    assert (cost.flops, cost.bytes_read, cost.bytes_written) == (5, 7, 3)
    assert cost.flops_effective == 4.0 and cost.dtype == "int8"

    assert costmodel.cost_for_bench_row({"phase": "selftest", "n": 1}) \
        is None  # the CI stub has no model, and that is fine
    assert costmodel.cost_for_bench_row({"phase": "decode"}) is None


# ---------------------------------------------------------------------------
# roofline: attribution math + the row stamp
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_roofline_attribute_math_by_hand():
    v5e = hwspec.spec("v5e")
    # memory-bound: intensity 2 flops/byte, far below the ~240 ridge
    c = Cost(flops=2.0e12, bytes_read=0.8e12, bytes_written=0.2e12)
    r = roofline.attribute(c, 10.0, v5e)
    assert r.bound == "memory"
    assert r.achieved_tbps == pytest.approx(0.1)
    assert r.achieved_tflops == pytest.approx(0.2)
    t_mem = 1.0e12 / (0.819e12)
    assert r.pct_roofline == pytest.approx(t_mem / 10.0)
    assert r.effective_pct_roofline == pytest.approx(r.pct_roofline)
    assert r.mfu == pytest.approx(0.2 / 197.0)
    assert r.intensity == pytest.approx(2.0)
    assert r.ridge == pytest.approx(197.0 / 0.819)

    # compute-bound at the int8 peak
    c = Cost(flops=394e12, bytes_read=1e9, bytes_written=0, dtype="int8")
    r = roofline.attribute(c, 2.0, v5e)
    assert r.bound == "compute"
    assert r.pct_roofline == pytest.approx(0.5)
    assert r.peak_tflops == pytest.approx(394.0)

    # effective work: waste shows up ONLY in the effective fraction
    c = Cost(flops=100e12, bytes_read=1e9, bytes_written=0,
             flops_effective=50e12)
    r = roofline.attribute(c, 1.0, v5e)
    assert r.effective_pct_roofline == pytest.approx(
        r.pct_roofline / 2.0)
    assert r.achieved_tflops_effective == pytest.approx(50.0)

    with pytest.raises(ValueError):
        roofline.attribute(c, 0.0, v5e)


def test_stamp_row_canonical_schema():
    row = {"phase": "prefill", "us": 100.0}
    cost = Cost(flops=1e9, bytes_read=1e6, bytes_written=1e5,
                flops_effective=8e8)
    out = roofline.stamp_row(row, cost, 1e-4, hwspec.spec("v5e"))
    assert out is row  # in place
    assert set(roofline.ROW_FIELDS) <= set(row)
    assert row["flops_effective"] == pytest.approx(8e8)
    assert row["bound"] in ("memory", "compute")
    assert 0 < row["pct_roofline"]
    assert row["effective_pct_roofline"] <= row["pct_roofline"]
    # no waste -> no redundant effective field on the banked row
    row2 = roofline.stamp_row({}, Cost(1e9, 1e6, 1e5), 1e-4,
                              hwspec.spec("v5e"))
    assert "flops_effective" not in row2


def test_spec_for_row_chip_then_peak_then_default():
    assert roofline.spec_for_row({"chip": "v5p"}).name == "v5p"
    assert roofline.spec_for_row({"peak": 0.819}).name == "v5e"
    assert roofline.spec_for_row({}).name == hwspec.DEFAULT_CHIP
    assert roofline.spec_for_row(
        {}, default=hwspec.spec("v6e")).name == "v6e"


def test_step_mode_stamp_is_identity():
    """roofline.stamp_row(step_mode=...) writes the serving-loop
    dispatch-structure identity: rows differing only in step_mode are
    DIFFERENT configurations to the audit (the num_splits precedent),
    while dispatch_residual_us is a measurement field."""
    cost = costmodel.serving_step(
        4, 128, 2, **costmodel.SERVING_SHAPES["llama70b_tp8shard_int8"])
    spec = hwspec.CHIP_SPECS["v5e"]
    rows = []
    for mode in ("fused", "per_op"):
        row = roofline.stamp_row(
            dict(phase="serving_fused", bs=4, ctx=128,
                 us_step=5000.0, dispatch_residual_us=100.0),
            cost, 5e-3, spec, step_mode=mode)
        assert row["step_mode"] == mode
        rows.append(row)
    k0, k1 = (bench_audit.row_key(r) for r in rows)
    assert k0 != k1
    r2 = dict(rows[0])
    r2["dispatch_residual_us"] = 999.0
    assert bench_audit.row_key(r2) == k0


def test_serving_ops_catalogued_and_cost_covered():
    """The serving steps are catalogued (L005) and cost-covered (obs
    doctor's uncovered list stays empty)."""
    from flashinfer_tpu.obs.catalog import API_OPS, METRICS, SERVING_OPS

    assert {"engine.step", "parallel.sharded_step"} <= SERVING_OPS \
        <= API_OPS
    assert "serve.step_retraces" in METRICS
    assert costmodel.API_OP_COSTS["parallel.sharded_step"] \
        == "serving_step_sharded"
    assert costmodel.uncovered_api_ops() == ()


def test_timeline_phase_mfu_joins_profiler_spans():
    spec = hwspec.spec("v5e")
    costs = {"attention": Cost(flops=1e9, bytes_read=1e8,
                               bytes_written=1e7)}
    events = [{"name": "serving.attention", "dur": 0.5e-3},
              {"name": "serving.attention", "dur": 0.5e-3},
              {"name": "serving.unmodeled", "dur": 1.0}]
    out = roofline.timeline_phase_mfu(events, costs, spec)
    assert set(out) == {"attention"}  # only phases with a cost
    assert out["attention"]["dur_s"] == pytest.approx(1e-3)  # summed
    assert out["attention"]["mfu"] == pytest.approx(
        1e9 / 1e-3 / 1e12 / 197.0, abs=1e-4)  # report rounds to 4 places


# ---------------------------------------------------------------------------
# bench_audit: the roofline-fraction comparison space
# ---------------------------------------------------------------------------


def test_roofline_fraction_normalizes_legacy_percent_rows():
    # pre-roofline scans rows banked PERCENT under the same field name
    # (no stamp fields ride along); stamped rows carry a 0..1 fraction.
    # Magnitude can't tell them apart — the banked history has a
    # 0.6-PERCENT gdn_decode artifact row that a >2.0 cutoff would
    # misread as a winning 0.6 fraction — so the stamp's presence does.
    assert bench_audit.roofline_fraction({"pct_roofline": 49.0}) \
        == pytest.approx(0.49)
    assert bench_audit.roofline_fraction({"pct_roofline": 0.6}) \
        == pytest.approx(0.006)  # the real banked artifact shape
    assert bench_audit.roofline_fraction(
        {"pct_roofline": 0.9, "bound": "memory"}) == pytest.approx(0.9)
    assert bench_audit.roofline_fraction(
        {"pct_roofline": 0.6, "chip": "v5e"}) == pytest.approx(0.6)
    assert bench_audit.roofline_fraction({"pct_roofline": 0}) is None
    assert bench_audit.roofline_fraction({}) is None


def test_auditor_compares_in_roofline_fraction_space_across_chips():
    """A v5p row must compete with the v5e history for the same
    configuration in fraction-of-own-roofline space — raw TB/s would
    call a 3x-faster chip 'ok' even when its kernel regressed."""
    hist = [{"phase": "decode", "bs": 64, "ctx": 4096, "tbps": 0.73,
             "pct_roofline": 0.89, "chip": "v5e"}]
    aud = bench_audit.RowAuditor(hist)
    # same fraction on the faster chip: ok, despite 3x the raw number
    good = aud.stamp({"phase": "decode", "bs": 64, "ctx": 4096,
                      "tbps": 2.4, "pct_roofline": 0.87, "chip": "v5p"})
    assert good["quality"] == "ok"
    assert good["vs_best_roofline"] == pytest.approx(0.87 / 0.89,
                                                     abs=1e-3)
    # 3x the raw v5e number but a collapsed fraction: poison — the raw
    # rule alone would have waved this regression through
    bad = bench_audit.RowAuditor(hist).stamp(
        {"phase": "decode", "bs": 64, "ctx": 4096, "tbps": 2.4,
         "pct_roofline": 0.25, "chip": "v5p"})
    assert bad["quality"] == "poison"


def test_auditor_poisons_measurements_above_the_hardware_ceiling():
    aud = bench_audit.RowAuditor()
    fast = aud.stamp({"phase": "serving", "bs": 64, "ctx": 4096,
                      "tbps": 1.6, "pct_roofline": 1.95,
                      "chip": "v5e"})
    assert fast["quality"] == "poison"
    # and the artifact never becomes the baseline best
    ok = aud.stamp({"phase": "serving", "bs": 64, "ctx": 4096,
                    "tbps": 0.7, "pct_roofline": 0.85, "chip": "v5e"})
    assert ok["quality"] == "ok"
    assert "vs_best_roofline" not in ok


def test_auditor_legacy_percent_artifact_rows_stay_poison():
    """The real banked shape the magnitude heuristic would misread: a
    gdn_decode row banked at 0.6 PERCENT of roofline (an artifact, raw
    gbps ~1% of best) must NOT read as a 0.60 fraction that beats the
    genuine ~0.52-0.58 history and re-audit 'ok'."""
    hist = [
        {"phase": "scans", "op": "gdn_decode", "B": 64, "gbps": 473.9,
         "pct_roofline": 57.9},  # genuine legacy row: 57.9 percent
        {"phase": "scans", "op": "gdn_decode", "B": 64, "gbps": 4.6,
         "pct_roofline": 0.6},  # artifact legacy row: 0.6 percent
    ]
    aud = bench_audit.RowAuditor(hist)
    bad = aud.stamp(dict(hist[1]))
    assert bad["quality"] == "poison"
    good = aud.stamp(dict(hist[0]))
    assert good["quality"] == "ok"


def test_auditor_raw_rule_still_works_without_fractions():
    aud = bench_audit.RowAuditor([{"phase": "moe", "tokens": 64,
                                   "tflops": 100.0}])
    row = aud.stamp({"phase": "moe", "tokens": 64, "tflops": 30.0})
    assert row["quality"] == "poison"  # 0.3 < 0.35, the committed rule
    assert row["vs_best"] == pytest.approx(0.3)


def test_load_banked_history_strict_raises_on_malformed(tmp_path):
    p = tmp_path / "BANK.md"
    p.write_text("# notes\n```json\n{not json]\n```\n"
                 "```json\n{\"rows\": [{\"phase\": \"x\"}, 17]}\n```\n")
    rows = bench_audit.load_banked_history(str(p))  # tolerant default
    assert rows == [{"phase": "x"}]
    with pytest.raises(ValueError) as e:
        bench_audit.load_banked_history(str(p), strict=True)
    assert "malformed json block" in str(e.value)
    assert "non-dict row" in str(e.value)
    with pytest.raises(ValueError):
        bench_audit.load_banked_history(str(tmp_path / "absent.md"),
                                        strict=True)


# ---------------------------------------------------------------------------
# the `obs perf` doctor
# ---------------------------------------------------------------------------


def _stamped(phase, us, cost, spec_name="v5e", **cfg):
    row = dict(phase=phase, us=us, **cfg)
    return roofline.stamp_row(row, cost, us * 1e-6,
                              hwspec.spec(spec_name))


def test_build_perf_report_sections_on_synthetic_rows():
    v5e = hwspec.spec("v5e")
    # a decode cell at half roofline; seconds from the cost itself
    dc = costmodel.paged_decode(64, 4096, 32, 8, 128)
    t_us = dc.bytes_total / (0.819e12) / 0.5 * 1e6
    rows = [_stamped("decode", t_us, dc, bs=64, ctx=4096)]
    # a prefill row with padding waste
    pf = Cost(flops=4e12, bytes_read=1e10, bytes_written=1e9,
              flops_effective=3e12, op="paged_prefill")
    rows.append(_stamped("prefill", 40000.0, pf, kind="paged_chunked",
                         bs=8, qlen=512, ctx=4096))
    # an implausibly fast artifact (above the ceiling): a PRE-roofline
    # row (no stamp — the auditor can't see a fraction), so only the
    # report's reconstruction-side ceiling check can catch it
    dc8 = costmodel.paged_decode(64, 8192, 32, 8, 128)
    rows.append(dict(phase="decode", bs=64, ctx=8192,
                     us=dc8.bytes_total / 0.819e12 / 1.25 * 1e6))
    # an e2e serving row joining the measured phase decomposition
    shape = costmodel.SERVING_SHAPES["llama70b_tp8shard_int8"]
    phases = costmodel.serving_phase_costs(64, 4096, 4, **shape)
    decomp = {}
    for name, c in phases.items():
        t = roofline.attribute(c, 1.0, v5e)
        floor = max(c.bytes_total / 0.819e12,
                    c.flops / (v5e.peak_tflops(c.dtype) * 1e12))
        decomp[name + "_us"] = floor / 0.5 * 1e6  # half roofline each
    decomp["residual_us"] = 12.0
    step = costmodel.serving_step(64, 4096, 4, **shape)
    srow = dict(phase="serving", model="llama70b_tp8shard_int8",
                mode="e2e_measured", bs=64, ctx=4096, layers=4,
                us_step=sum(v for k, v in decomp.items()
                            if k != "residual_us") + 12.0,
                overhead_decomposition=decomp)
    roofline.stamp_row(srow, step, srow["us_step"] * 1e-6, v5e)
    rows.append(srow)

    rep = roofline.build_perf_report(rows)
    assert rep["schema"] == "flashinfer_tpu.obs.perf/6"
    assert rep["rows_total"] == 4
    assert rep["rows_implausible"] == 1  # the artifact was dropped
    assert rep["rows_attributed"] == 3
    assert "v5e" in rep["chips"]

    by_op = {o["op"]: o for o in rep["ops"]}
    assert by_op["decode"]["bound"] == "memory"
    assert by_op["decode"]["pct_roofline"]["best"] == pytest.approx(
        0.5, abs=0.01)
    assert sum(o["time_share"] for o in rep["ops"]) == pytest.approx(
        1.0, abs=0.01)

    # waste attribution picked up the launched-vs-effective split
    assert len(rep["waste"]) == 1
    assert rep["waste"][0]["waste_pct"] == pytest.approx(25.0)

    # per-phase serving MFU joined every measured phase
    assert len(rep["serving_phase_mfu"]) == 1
    sp = rep["serving_phase_mfu"][0]
    assert set(sp["phases"]) == set(costmodel.SERVING_PHASES)
    for p in sp["phases"].values():
        assert p["pct_roofline"] == pytest.approx(0.5, abs=0.02)
    assert sp["residual_us"] == 12.0

    # offenders are ranked by severity = below-roofline x time share
    sev = [w["severity"] for w in rep["worst_offenders"]]
    assert sev == sorted(sev, reverse=True)

    # the human rendering covers every section without crashing
    text = roofline.render_perf_report(rep)
    assert "worst offenders" in text and "padding/pruning waste" in text
    assert "serving phase MFU" in text


# The headline cells' rows in the banked-row format, as bench.py wrote
# them on one v5e day before PR 1 (the record itself is gone).  They
# test the reduction from rows to fractions; they say nothing about
# today's code.  The 0.0378 row is the degraded window the auditor
# must drop.
_HEADLINE_ROWS = [
    dict(phase="decode", bs=64, ctx=4096, us=us, tbps=tbps, peak=0.819)
    for us, tbps in ((1443.0, 0.7448), (1473.6, 0.7294), (1497.3, 0.7178),
                     (1481.6, 0.7254), (28409.8, 0.0378))
] + [
    dict(phase="prefill", kind="paged_chunked", bs=8, qlen=512, ctx=4096,
         us=8534.2, tflops=30.2),
    dict(phase="prefill", kind="ragged_flash", qlen=8192, us=9994.5,
         tflops=55.01),
    dict(phase="mla", bs=64, ctx=4096, heads=128, us=1328.2, tbps=0.2526,
         peak=0.819),
    dict(phase="mla", bs=64, ctx=4096, heads=128, us=1307.5, tbps=0.2566,
         peak=0.819),
]


def test_perf_cli_reproduces_round5_headline_fractions(tmp_path):
    """Acceptance: `obs perf --banked FILE` reproduces the VERDICT
    numbers (decode 87.6-90.9% of the v5e HBM roofline, prefill MFU
    15-28%, MLA ~31-33%) from banked rows with no hand math, and the
    JSON form is schema-stable."""
    bank = tmp_path / "BANK.md"
    bank.write_text("```json\n" + json.dumps({"rows": _HEADLINE_ROWS})
                    + "\n```\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "perf",
         "--banked", str(bank), "--json"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    rep = json.loads(p.stdout)
    assert rep["schema"] == "flashinfer_tpu.obs.perf/6"
    assert {"chips", "rows_total", "rows_attributed", "ops",
            "worst_offenders", "waste", "serving_phase_mfu",
            "serving_ici", "scaling_prediction", "serving_disagg",
            "headline"} <= set(rep)
    assert rep["serving_disagg"]["predicted_kv_migrate"][
        "ici_bytes_per_request"] > 0
    # every fixture row but the degraded window (audited out)
    assert rep["rows_attributed"] == len(_HEADLINE_ROWS) - 1
    h = rep["headline"]
    dec = h["decode_bs64_ctx4k_pct_roofline"]
    assert 0.86 <= dec["min"] <= 0.89 and 0.89 <= dec["max"] <= 0.92
    mfu = h["prefill_mfu"]
    assert 0.13 <= mfu["min"] <= 0.17 and 0.26 <= mfu["max"] <= 0.30
    mla = h["mla_pct_roofline"]
    assert 0.29 <= mla["min"] <= mla["max"] <= 0.36
    for o in rep["ops"]:  # schema of every table row
        assert {"op", "rows", "bound", "chip", "dtype", "intensity",
                "pct_roofline", "effective_pct_roofline",
                "best_achieved", "time_share"} <= set(o)
        assert o["bound"] in ("memory", "compute")
        assert 0 < o["pct_roofline"]["best"] <= 1.05


def test_perf_cli_exits_nonzero_on_malformed_bank(tmp_path):
    bad = tmp_path / "BAD.md"
    bad.write_text("```json\n{oops\n```\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "flashinfer_tpu.obs", "perf",
         "--banked", str(bad)],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300,
    )
    assert p.returncode == 2
    assert "malformed" in p.stderr


# ---------------------------------------------------------------------------
# coverage + zero-overhead pins
# ---------------------------------------------------------------------------


def test_every_api_op_has_a_costmodel_family():
    """Mirrors analysis L005: a decorated public op with no cost-model
    family would bench but never roofline-attribute.  New @flashinfer_api
    ops must be added to costmodel.API_OP_COSTS (the doctor lists the
    stragglers)."""
    assert costmodel.uncovered_api_ops() == ()
    # every named family is a real formula in the module
    for fam in set(costmodel.API_OP_COSTS.values()):
        assert callable(getattr(costmodel, fam)), fam


def test_zero_overhead_cost_model_never_loads_in_plain_use():
    """Disabled-path pin: with metrics off and no bench/report running,
    plain library use never even imports the cost model or the
    roofline module — zero attribution arithmetic on any hot path."""
    code = (
        "import sys, jax.numpy as jnp\n"
        "import flashinfer_tpu as fi\n"
        "x = jnp.ones((4, 8), jnp.float32)\n"
        "w = jnp.ones((8,), jnp.float32)\n"
        "fi.rmsnorm(x, w)\n"
        "fi.silu_and_mul(jnp.ones((4, 16), jnp.float32))\n"
        "bad = [m for m in sys.modules if m in ("
        "'flashinfer_tpu.obs.costmodel', 'flashinfer_tpu.obs.roofline')]\n"
        "assert not bad, bad\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLASHINFER_TPU_METRICS", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert p.returncode == 0, (p.stdout + p.stderr)[-2000:]
