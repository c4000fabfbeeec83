"""Roofline attribution: cost x wall time x chip spec -> efficiency.

The joining half of the roofline model (Williams et al., CACM 2009):
:func:`attribute` takes a :class:`~flashinfer_tpu.obs.costmodel.Cost`,
a measured wall time, and a :class:`~flashinfer_tpu.obs.hwspec.ChipSpec`
and answers the only performance question that is portable across chip
generations — *what fraction of the binding hardware ceiling did this
run achieve?*

- ``t_mem = bytes_total / peak_HBM`` and ``t_comp = flops / peak_MXU``
  are the two roofline floors; the larger is the binding one
  (``bound`` = ``"memory"`` | ``"compute"``, decided by the op's
  arithmetic intensity vs the chip's ridge point).
- ``pct_roofline = max(t_mem, t_comp) / t_measured`` — 1.0 means the
  op runs exactly at the hardware ceiling for its *launched* work.
- ``effective_pct_roofline`` is the same fraction counting only
  *effective* (useful) work — for the fused work-unit prefill the gap
  to ``pct_roofline`` is exactly the padding/pruning waste PR 3's
  packing exists to shrink.  Equal when the op has no waste.

:func:`stamp_row` writes the canonical field set onto a bench row;
:func:`build_perf_report` is the ``obs perf`` doctor — it reproduces
the round-5 VERDICT analysis (per-op efficiency, bound classification,
worst offenders by pct-below-roofline x time share, waste attribution,
per-serving-phase MFU) from banked rows with no hand math.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import (Dict, Iterable, List, Mapping, Optional, Sequence,
                    Tuple)

from flashinfer_tpu.obs import costmodel, hwspec
from flashinfer_tpu.obs.costmodel import Cost
from flashinfer_tpu.obs.hwspec import ChipSpec

# the canonical roofline field set every stamped bench row carries
ROW_FIELDS = ("flops", "bytes_read", "bytes_written", "intensity",
              "bound", "pct_roofline", "effective_pct_roofline", "chip",
              "dtype")

# The BASELINE.md tracked-metric cells the VERDICT headline fractions
# quote (bench.py's non-sweep default configurations).  ``obs perf``
# computes its headline ranges over ok-quality rows of exactly these
# cells — the sweep grid's other cells inform the efficiency table but
# never the headline, matching how the round-5 numbers were derived.
HEADLINE_CELLS: Dict[str, tuple] = {
    "decode": ({"bs": 64, "ctx": 4096},),
    "prefill": (
        {"kind": "paged_chunked", "bs": 8, "qlen": 512, "ctx": 4096},
        {"kind": "ragged_flash", "qlen": 8192},
    ),
    "mla": ({"bs": 64, "ctx": 4096},),
}


@dataclasses.dataclass(frozen=True)
class RooflineResult:
    chip: str
    dtype: str
    achieved_tflops: float  # launched flops / t
    achieved_tflops_effective: float  # useful flops / t (== launched
    # when the op has no padding/pruning waste) — the number every
    # banked ``tflops`` field reports
    achieved_tbps: float  # launched bytes / t
    intensity: float  # flops/byte, launched
    ridge: float  # chip ridge point at this dtype
    bound: str  # "memory" | "compute" | "ici"
    pct_roofline: float  # fraction of the binding roofline, launched
    effective_pct_roofline: float  # same, useful work only
    mfu: float  # achieved_tflops / peak_tflops (launched)
    peak_tflops: float
    peak_tbps: float
    # the ICI dimension (0 for single-chip ops): fraction of the
    # measured time the predicted collective floor explains, and the
    # chip's interconnect ceiling it was priced against
    pct_ici_roofline: float = 0.0
    peak_ici_gbps: float = 0.0


def attribute(cost: Cost, seconds: float, spec: ChipSpec) -> RooflineResult:
    """Join one cost with one measured wall time on one chip.

    Three floors: HBM transfer, MXU compute, and — when the cost
    carries collective traffic (``ici_bytes``, the sharded serving
    families) — the ICI wire floor.  ``bound`` names the deepest one;
    ``pct_roofline`` keeps its meaning (binding floor / measured) with
    the ICI floor folded into the max."""
    if seconds <= 0:
        raise ValueError(f"seconds must be positive, got {seconds}")
    peak_tflops = spec.peak_tflops(cost.dtype)
    peak_tbps = spec.hbm_tbps
    t_mem = cost.bytes_total / (peak_tbps * 1e12)
    t_comp = cost.flops / (peak_tflops * 1e12)
    t_ici = cost.ici_bytes / (spec.ici_gbps * 1e9) \
        if cost.ici_bytes else 0.0
    if t_ici > max(t_mem, t_comp):
        bound = "ici"
    else:
        bound = "memory" if t_mem >= t_comp else "compute"
    eff_flops = cost.effective_flops
    t_roof_eff = max(t_mem, eff_flops / (peak_tflops * 1e12), t_ici)
    return RooflineResult(
        chip=spec.name, dtype=hwspec.normalize_dtype(cost.dtype),
        achieved_tflops=cost.flops / seconds / 1e12,
        achieved_tflops_effective=eff_flops / seconds / 1e12,
        achieved_tbps=cost.bytes_total / seconds / 1e12,
        intensity=cost.intensity,
        ridge=spec.ridge_intensity(cost.dtype),
        bound=bound,
        pct_roofline=max(t_mem, t_comp, t_ici) / seconds,
        effective_pct_roofline=t_roof_eff / seconds,
        mfu=cost.flops / seconds / 1e12 / peak_tflops,
        peak_tflops=peak_tflops, peak_tbps=peak_tbps,
        pct_ici_roofline=t_ici / seconds,
        peak_ici_gbps=spec.ici_gbps,
    )


def stamp_row(row: Dict, cost: Cost, seconds: float,
              spec: ChipSpec, *, num_splits: Optional[int] = None,
              merge_bytes: Optional[float] = None,
              step_mode: Optional[str] = None,
              mesh_axes: Optional[str] = None,
              attention_backend: Optional[str] = None,
              fused_ingest: Optional[bool] = None,
              ingest_bytes_avoided: Optional[float] = None) -> Dict:
    """Write the canonical roofline fields onto a bench row in place.
    The uniform schema is what makes ``obs perf`` and the auditor's
    roofline-fraction rule possible.

    ``num_splits``/``merge_bytes`` are the split-KV decode metadata
    (docs/observability.md): ``num_splits`` is part of the row's
    configuration identity (rows at different split factors never
    compete in the quality audit); ``merge_bytes`` is the cost model's
    partial-state traffic term (``costmodel.decode_split_breakdown``),
    a derived measurement field.

    ``step_mode`` is the serving-loop dispatch-structure identity
    (``"fused"`` — the compile-once donated serve/step.py program —
    vs ``"per_op"``, the per-phase jitted micro-loop): like
    num_splits it is CONFIGURATION, so the two serving-loop shapes
    keep separate audit histories even at identical model shapes.

    ``mesh_axes`` is the mesh-shape identity of a SHARDED row
    (``ShardingPlan.mesh_axes``, e.g. ``"dp1.tp8"``): configuration
    like step_mode — a tp8 row must never compete with tp1 history.
    Costs carrying collective traffic additionally stamp ``ici_bytes``
    and ``pct_ici_roofline`` (measurement fields: the predicted ICI
    wire bytes and the fraction of measured time the ICI floor
    explains).

    ``attention_backend`` is the serving-engine attention-tier
    identity (``"reference"`` — the dense XLA oracle — vs
    ``"kernel"`` — the Pallas work-unit lowering,
    serve/engine_kernels.py): configuration like step_mode, so a
    kernel-tier row never competes with reference-row history in the
    quality audit.

    ``fused_ingest`` is the prefill ingest-mode identity (the ISSUE 14
    RoPE + quantize-append fusion, ops/paged_prefill.py): configuration
    like step_mode/attention_backend, so an A/B pair's fused and
    separate rows keep separate banked histories and never compete.
    ``ingest_bytes_avoided`` is the cost model's predicted avoided-HBM
    delta for the row's shape (``costmodel.prefill_ingest_breakdown``)
    — derived, a MEASUREMENT field like merge_bytes."""
    res = attribute(cost, seconds, spec)
    if num_splits is not None:
        row["num_splits"] = int(num_splits)
    if merge_bytes is not None:
        row["merge_bytes"] = float(merge_bytes)
    if step_mode is not None:
        row["step_mode"] = str(step_mode)
    if mesh_axes is not None:
        row["mesh_axes"] = str(mesh_axes)
    if attention_backend is not None:
        row["attention_backend"] = str(attention_backend)
    if fused_ingest is not None:
        row["fused_ingest"] = bool(fused_ingest)
    if ingest_bytes_avoided is not None:
        row["ingest_bytes_avoided"] = float(ingest_bytes_avoided)
    if cost.ici_bytes:
        row["ici_bytes"] = float(cost.ici_bytes)
        row["pct_ici_roofline"] = round(res.pct_ici_roofline, 4)
    row["flops"] = float(cost.flops)
    row["bytes_read"] = float(cost.bytes_read)
    row["bytes_written"] = float(cost.bytes_written)
    row["intensity"] = round(res.intensity, 3)
    row["bound"] = res.bound
    row["pct_roofline"] = round(res.pct_roofline, 4)
    row["effective_pct_roofline"] = round(res.effective_pct_roofline, 4)
    row["chip"] = res.chip
    row["dtype"] = res.dtype
    # self-describing rows: a banked row re-attributes with no shape
    # reconstruction (costmodel.cost_from_stamped_row), so the waste
    # split must ride along when it exists
    if cost.flops_effective is not None \
            and cost.flops_effective != cost.flops:
        row["flops_effective"] = float(cost.flops_effective)
    return row


def spec_for_row(row: Mapping,
                 default: Optional[ChipSpec] = None) -> ChipSpec:
    """The chip a banked row was measured on: its ``chip`` field, else
    its ``peak`` (HBM TB/s) mapped back through the registry, else
    `default` (v5e — every pre-roofline banked row)."""
    if row.get("chip"):
        return hwspec.spec(str(row["chip"]))
    s = hwspec.spec_for_peak_tbps(row.get("peak"))
    if s is not None:
        return s
    return default or hwspec.CHIP_SPECS[hwspec.DEFAULT_CHIP]


def timeline_phase_mfu(events: Iterable[Mapping],
                       phase_costs: Mapping[str, Cost],
                       spec: ChipSpec,
                       prefix: str = "serving.") -> Dict[str, dict]:
    """Join profiler timeline spans with per-phase costs: aggregate
    span durations by name (stripping `prefix`) and attribute each
    phase that has a cost.  The device-trace cross-check for the
    micro-loop decomposition numbers."""
    durs: Dict[str, float] = {}
    for e in events:
        name = str(e.get("name", ""))
        if name.startswith(prefix):
            name = name[len(prefix):]
        durs[name] = durs.get(name, 0.0) + float(e.get("dur", 0.0))
    out: Dict[str, dict] = {}
    for phase, cost in phase_costs.items():
        t = durs.get(phase)
        if t and t > 0:
            res = attribute(cost, t, spec)
            out[phase] = {"dur_s": t, "mfu": round(res.mfu, 4),
                          "bound": res.bound,
                          "pct_roofline": round(res.pct_roofline, 4)}
    return out


# -------------------------------------------------------------------------
# `obs perf`: the doctor report over banked bench rows
# -------------------------------------------------------------------------


def _row_group(row: Mapping) -> str:
    """Stable per-op grouping key for the efficiency table."""
    parts = [str(row.get("phase"))]
    for f in ("kind", "op", "variant", "backend", "mode", "layout",
              "step_mode", "mesh_axes"):
        if row.get(f) is not None:
            parts.append(f"{row[f]}")
    return "/".join(parts)


# -------------------------------------------------------------------------
# ICI-aware predictions: per-phase collective attribution + the
# tp1->tp8 scaling curve per chip generation (the before-hardware
# multi-chip story: dryrun + model instead of blocked on the driver)
# -------------------------------------------------------------------------

SCALING_CHIPS = ("v5e", "v5p")
SCALING_TPS = (1, 2, 4, 8)
# the canonical sharded serving cell the predictions quote (the
# BASELINE.md serving north star at full model depth)
SCALING_CELL = dict(bs=64, ctx=4096, layers=80, model="llama70b_int8")


def predict_serving_scaling(*, bs: int = 64, ctx: int = 4096,
                            layers: int = 80,
                            model: str = "llama70b_int8",
                            chips: Sequence[str] = SCALING_CHIPS,
                            tps: Sequence[int] = SCALING_TPS) -> dict:
    """Predicted tp scaling of the sharded serving step per chip gen:
    for each tp, the roofline-forward step time (HBM/MXU floor + serial
    ICI floor, ``costmodel.predict_step_seconds``) of the PER-CHIP
    shard, plus speedup vs tp1 and scaling efficiency (speedup/tp —
    the number that says where ICI starts eating the linear win)."""
    shape = costmodel.SHARDED_SERVING_SHAPES[model]
    out: Dict[str, dict] = {}
    for chip in chips:
        spec = hwspec.spec(chip)
        rows: Dict[str, dict] = {}
        t1 = None
        for tp in tps:
            cost = costmodel.serving_step_sharded(
                bs, ctx, layers, dp=1, tp=tp, **shape)
            t = costmodel.predict_step_seconds(
                cost, hbm_tbps=spec.hbm_tbps,
                peak_tflops=spec.peak_tflops(cost.dtype),
                ici_gbps=spec.ici_gbps)
            t_ici = cost.ici_bytes / (spec.ici_gbps * 1e9)
            if t1 is None:
                t1 = t
            res = attribute(cost, t, spec)
            rows[str(tp)] = {
                "pred_us": round(t * 1e6, 1),
                "ici_us": round(t_ici * 1e6, 2),
                "ici_bytes": cost.ici_bytes,
                "bound": res.bound,
                "speedup_vs_tp1": round(t1 / t, 3),
                "scaling_efficiency": round(t1 / t / tp, 3),
            }
        out[spec.name] = rows
    return out


def predict_kv_migrate(*, ctx: int = 4096, layers: int = 80,
                       model: str = "llama70b_int8",
                       chips: Sequence[str] = SCALING_CHIPS) -> dict:
    """Predicted prefill->decode KV handoff cost per REQUEST at one
    context length: the ``costmodel.kv_migrate`` page-run x
    kv-byte-width wire formula priced per chip generation — the
    before-hardware half of the disaggregated serving story
    (serve/kv_tier.py), joined against measured migration stamps by
    the perf/3 ``serving_disagg`` section."""
    shape = costmodel.SHARDED_SERVING_SHAPES[model]
    cost = costmodel.kv_migrate(
        ctx, page_size=shape["page_size"], num_kv_heads=shape["hkv"],
        head_dim=shape["hd"], layers=layers,
        kv_bytes=shape["kv_bytes"])
    return {
        "model": model, "ctx": ctx, "layers": layers,
        "ici_bytes_per_request": cost.ici_bytes,
        "pred_ici_us": {
            hwspec.spec(c).name: round(
                cost.ici_bytes / (hwspec.spec(c).ici_gbps * 1e9) * 1e6,
                2)
            for c in chips},
    }


# the headline prefill cells' ingest geometry: (name, total_q,
# total_kv, HQ, HKV, D) — the bench.py prefill phase shapes the VERDICT
# fractions quote (HEADLINE_CELLS), flattened to token totals
_INGEST_CELLS = (
    ("paged_bs8_q512_ctx4096", 8 * 512, 8 * 4096, 32, 8, 128),
    ("ragged_T8192", 8192, 8192, 32, 8, 128),
)


def predict_prefill_ingest(*, chips: Sequence[str] = SCALING_CHIPS,
                           cells: Sequence[tuple] = _INGEST_CELLS) -> dict:
    """The perf/4 prefill-ingest section, predicted half: for each
    headline prefill cell, the separate-vs-fused modeled HBM bytes
    (``costmodel.prefill_ingest_breakdown``) and the per-chip chooser
    verdict (``predict_prefill_ingest_win`` — the rule that decides the
    ``prefill.fused_ingest`` knob default).  The ISSUE 14 acceptance
    bar — headline shapes drop >= 20% of modeled HBM bytes — is read
    straight off ``avoided_fraction`` here."""
    out: Dict[str, dict] = {}
    for name, tq, tkv, hq, hkv, hd in cells:
        bd = costmodel.prefill_ingest_breakdown(tq, tkv, hq, hkv, hd)
        verdicts = {}
        for chip in chips:
            spec = hwspec.spec(chip)
            use, ev = costmodel.predict_prefill_ingest_win(
                tq, tkv, hq, hkv, hd, hbm_tbps=spec.hbm_tbps,
                peak_tflops=spec.peak_tflops("bf16"))
            verdicts[spec.name] = {
                "use_fused": use,
                "pred_sep_us": round(ev["separate_s"] * 1e6, 1),
                "pred_fused_us": round(ev["fused_s"] * 1e6, 1),
            }
        out[name] = {
            "separate_bytes": bd["separate_bytes"],
            "fused_bytes": bd["fused_bytes"],
            "bytes_avoided": bd["bytes_avoided"],
            "avoided_fraction": round(bd["avoided_fraction"], 4),
            "chips": verdicts,
        }
    return out


def _prefill_ingest(attributed: Sequence[Mapping]) -> dict:
    """The perf/4 prefill-ingest section: the predicted byte drop per
    headline cell joined with every banked prefill row that carries the
    ingest identity stamp (``fused_ingest`` + ``ingest_bytes_avoided``,
    the bench prefill A/B pair) — so the MFU table's effective-vs-
    launched story shows what the fusion accounted for."""
    measured: List[dict] = []
    for a in attributed:
        row = a["row"]
        # both A/B harnesses join: bench.py's prefill phase pair AND
        # the bench_prefill_blocks.py --sweep-ingest cells
        if row.get("phase") not in ("prefill", "prefill_blocks") \
                or row.get("fused_ingest") is None:
            continue
        m = {k: row[k] for k in (
            "kind", "bs", "qlen", "ctx", "fused_ingest",
            "ingest_bytes_avoided", "us", "tflops", "bound",
            "pct_roofline", "effective_pct_roofline", "chip")
            if row.get(k) is not None}
        measured.append(m)
    return {"predicted": predict_prefill_ingest(), "rows": measured}


def predict_serving_ici(*, bs: int = 64, ctx: int = 4096,
                        layers: int = 80, tp: int = 8, dp: int = 1,
                        model: str = "llama70b_int8",
                        chips: Sequence[str] = SCALING_CHIPS) -> dict:
    """Per-serving-phase predicted collective traffic and wire time at
    one mesh shape: which phase's collectives cost what, per chip gen —
    the attribution half of the ICI dimension (`obs perf`).  The
    ``kv_migrate`` key rides alongside the per-step phases: the
    PER-REQUEST prefill->decode handoff wire cost of the disaggregated
    tier at the same cell (it is not a per-step collective, so it
    never joins the phase sum)."""
    shape = costmodel.SHARDED_SERVING_SHAPES[model]
    phases = costmodel.serving_phase_costs_sharded(
        bs, ctx, layers, dp=dp, tp=tp, **shape)
    table: Dict[str, dict] = {}
    for name in costmodel.SERVING_PHASES:
        cost = phases[name]
        if not cost.ici_bytes:
            continue
        table[name] = {
            "ici_bytes": cost.ici_bytes,
            "pred_ici_us": {
                hwspec.spec(c).name: round(
                    cost.ici_bytes / (hwspec.spec(c).ici_gbps * 1e9)
                    * 1e6, 2)
                for c in chips},
        }
    return {"model": model, "bs": bs, "ctx": ctx, "layers": layers,
            "mesh_axes": f"dp{dp}.tp{tp}", "phases": table,
            "kv_migrate": predict_kv_migrate(
                ctx=ctx, layers=layers, model=model, chips=chips)}


def _attributed_rows(rows: Sequence[Mapping],
                     default_spec: Optional[ChipSpec] = None
                     ) -> Tuple[List[dict], int]:
    """Attribute every attributable row: stamped fields when present,
    else the cost model's reconstruction from config.  Returns
    ``(attributed, n_implausible)``.

    Every row is first RE-audited against the full history (a
    :class:`~flashinfer_tpu.obs.bench_audit.RowAuditor` seeded with all
    rows): pre-stamping banked rows carry no ``quality`` field, and an
    emit-time ``ok`` can become retroactively implausible once later
    runs measured the same cell 3x faster.  Re-audited poison rows are
    dropped, and so is any row whose attributed fraction exceeds the
    binding hardware ceiling (pre-roofline banked rows carry no
    ``pct_roofline`` for the auditor's own too-fast rule to see) — the
    report never quotes a machine-flagged artifact."""
    from flashinfer_tpu.obs import bench_audit

    auditor = bench_audit.RowAuditor(rows)
    out: List[dict] = []
    implausible = 0
    for row in rows:
        quality = auditor.stamp(dict(row)).get("quality", "ok")
        if quality == "poison":
            continue  # machine-flagged artifacts never drive analysis
        spec = spec_for_row(row, default_spec)
        rec = costmodel.cost_for_bench_row(row)
        if rec is None:
            continue
        cost, seconds = rec
        if not (seconds > 0):
            continue
        res = attribute(cost, seconds, spec)
        if res.pct_roofline > bench_audit.IMPLAUSIBLY_FAST_ROOFLINE:
            implausible += 1
            continue
        out.append({
            "group": _row_group(row), "phase": row.get("phase"),
            "row": dict(row), "seconds": seconds, "cost": cost,
            "res": res, "quality": quality,
        })
    return out, implausible


def _in_headline_cell(a: Mapping) -> bool:
    cells = HEADLINE_CELLS.get(a["phase"], ())
    return any(all(a["row"].get(k) == v for k, v in cell.items())
               for cell in cells)


def _headline(attributed: List[dict]) -> dict:
    """The round-5 VERDICT fractions, recomputed — no hand math.
    Ranges run over ok-quality rows of the HEADLINE_CELLS only (the
    tracked-metric configurations), exactly the rows the VERDICT
    quoted: decode across repeated runs of the bs64/ctx4k cell,
    prefill MFU across the paged + ragged headline shapes, MLA across
    both layouts of its headline cell."""
    ok = [a for a in attributed
          if a["quality"] == "ok" and _in_headline_cell(a)]

    def fracs(phase, eff=False):
        return sorted(
            (a["res"].effective_pct_roofline if eff
             else a["res"].pct_roofline)
            for a in ok if a["phase"] == phase)

    decode = fracs("decode")
    prefill = fracs("prefill", eff=True)
    mla = fracs("mla")
    h: dict = {}
    if decode:
        h["decode_bs64_ctx4k_pct_roofline"] = {
            "min": round(decode[0], 4), "max": round(decode[-1], 4)}
    if prefill:
        h["prefill_mfu"] = {"min": round(prefill[0], 4),
                            "max": round(prefill[-1], 4)}
    if mla:
        h["mla_pct_roofline"] = {"min": round(mla[0], 4),
                                 "max": round(mla[-1], 4)}
    return h


def _serving_disagg(attributed: Sequence[Mapping]) -> dict:
    """The perf/3 disaggregation section: the predicted per-request
    ``kv_migrate`` wire cost at the canonical cell, joined against
    every banked ``serving_disagg`` row's MEASURED migration stamps
    (``migrate_bytes`` / ``migrate_us`` are measurement fields the
    bench phase emits).  ``measured_vs_pred_wire`` > 1 means the real
    handoff ran slower than the ICI floor — the gap is scheduling +
    staging overhead, exactly what the disagg session must shrink."""
    pred = predict_kv_migrate(
        ctx=SCALING_CELL["ctx"], layers=SCALING_CELL["layers"],
        model=SCALING_CELL["model"])
    measured: List[dict] = []
    for a in attributed:
        row = a["row"]
        if row.get("phase") != "serving_disagg":
            continue
        m = {k: row[k] for k in (
            "mode", "migrations", "migrate_bytes", "migrate_us",
            "spills", "restores", "recomputes", "ici_bytes",
            "pct_ici_roofline", "bound", "chip")
            if row.get(k) is not None}
        mb = row.get("migrate_bytes")
        if isinstance(mb, (int, float)) and mb > 0:
            spec = spec_for_row(row)
            wire_us = mb / (spec.ici_gbps * 1e9) * 1e6
            m["pred_wire_us"] = round(wire_us, 2)
            mu = row.get("migrate_us")
            if isinstance(mu, (int, float)) and mu > 0 and wire_us > 0:
                m["measured_vs_pred_wire"] = round(mu / wire_us, 3)
        measured.append(m)
    return {"predicted_kv_migrate": pred, "rows": measured}


def _host_loop(attributed: Sequence[Mapping]) -> dict:
    """The perf/5 host-loop section: the step-loop flight deck's
    host-gap decomposition joined from two directions.

    Banked side: serving rows stamped with ``host_frac`` /
    ``host_gap_us`` / ``pred_step_ratio`` (bench measurement fields,
    never identity) each get the Amdahl projection ``1 / (1 -
    host_frac)`` — the speedup CEILING ROADMAP item 4's host/device
    pipeline refactor can buy for that cell (the host work still
    exists, it just overlaps; real wins land below the ceiling).

    Live side: when the calling process has already loaded the steploop
    ledger (``obs steploop --selftest``, an instrumented run ending in
    ``obs perf``), its summary joins as ``live`` — real ledger data.
    The module is looked up, NEVER imported: a plain ``obs perf`` over
    banked rows keeps the zero-overhead default intact."""
    import sys as _sys

    measured: List[dict] = []
    for a in attributed:
        row = a["row"]
        hf = row.get("host_frac")
        if hf is None or not str(row.get("phase", "")).startswith(
                "serving"):
            continue
        m = {k: row[k] for k in (
            "phase", "model", "mode", "variant", "step_mode",
            "attention_backend", "bs", "ctx", "us_step", "host_gap_us",
            "host_frac", "pred_step_ratio", "chip")
            if row.get(k) is not None}
        m["amdahl_ceiling"] = round(1.0 / max(1.0 - float(hf), 1e-3), 3)
        measured.append(m)
    out: dict = {"rows": measured}
    if measured:
        worst = max(measured, key=lambda m: float(m["host_frac"]))
        fracs = sorted(float(m["host_frac"]) for m in measured)
        out["host_frac_median"] = round(fracs[len(fracs) // 2], 4)
        out["worst"] = {
            "phase": worst.get("phase"), "mode": worst.get("mode"),
            "host_frac": worst["host_frac"],
            "amdahl_ceiling": worst["amdahl_ceiling"],
        }
    sl = _sys.modules.get("flashinfer_tpu.obs.steploop")
    if sl is not None:
        s = sl.summarize()
        if s.get("steps"):
            out["live"] = {
                "steps": s["steps"],
                "idle_ticks": s["idle_ticks"],
                "surfaces": s["surfaces"],
                "host_frac": s["host_frac"],
                "overlap_efficiency": s["overlap_efficiency"],
                "amdahl_ceiling": s["amdahl_ceiling"],
                "worst_phase": s["worst_phase"],
                "phases_us": s["phases"],
            }
    return out


def _graduation(attributed: Sequence[Mapping]) -> dict:
    """The perf/6 graduation section: per tuning-config section, where
    it stands in the hardware graduation pipeline —

    - ``measured``: provenance already flipped by ``obs bringup
      --graduate`` (carries journal_id + banked_row references that
      L006 requires),
    - ``quarantined``: a bring-up smoke-ladder rung that feeds this
      section wedged the chip (the quarantine entry's ``bench_phases``
      intersect the section's banked phases),
    - ``pending``: still shipping seed/model-derived tactics.

    Plus the predicted-vs-measured audit join ROADMAP item 1 demands:
    for each perf/2–perf/4 prediction family, how many banked rows of
    its measuring phase exist — the count that turns a prediction
    section from forecast into audit."""
    try:
        from flashinfer_tpu.obs import bringup
        section_phases = bringup.SECTION_BANK_PHASES
        quarantined_phases = set(bringup.quarantined_bench_phases())
        cfg_dir = bringup._default_configs_dir()
    except Exception:
        return {"sections": [], "audit": {}}
    sections: List[dict] = []
    try:
        cfg_files = sorted(fn for fn in os.listdir(cfg_dir)
                           if fn.endswith(".json"))
    except OSError:
        cfg_files = []
    for fn in cfg_files:
        try:
            cfg = json.loads(open(os.path.join(cfg_dir, fn)).read())
        except Exception:
            continue
        for name, sec in sorted(cfg.items()):
            if not isinstance(sec, dict) or "tactics" not in sec \
                    or name == "tactics":
                continue
            phases = section_phases.get(name, (name,))
            if sec.get("provenance") == "measured":
                status = "measured"
            elif quarantined_phases.intersection(phases):
                status = "quarantined"
            else:
                status = "pending"
            entry = {
                "chip": fn[:-5], "section": name, "status": status,
                "provenance": sec.get("provenance"),
                "tactics": len(sec.get("tactics") or {}),
            }
            if sec.get("journal_id"):
                entry["journal_id"] = sec["journal_id"]
            if sec.get("banked_row"):
                entry["banked_row"] = sec["banked_row"]
            sections.append(entry)
    # audit join: prediction family -> measured banked rows by phase
    by_phase: Dict[str, int] = {}
    for a in attributed:
        ph = a["row"].get("phase")
        if isinstance(ph, str):
            by_phase[ph] = by_phase.get(ph, 0) + 1
    audit = {
        "serving_ici": {"predicted_schema": "perf/2",
                        "measured_rows": by_phase.get("serving_sharded", 0)},
        "serving_disagg": {"predicted_schema": "perf/3",
                           "measured_rows": by_phase.get(
                               "serving_disagg", 0)},
        "prefill_ingest": {"predicted_schema": "perf/4",
                           "measured_rows": by_phase.get("prefill", 0)},
        "host_loop": {"predicted_schema": "perf/5",
                      "measured_rows": sum(
                          n for ph, n in by_phase.items()
                          if ph.startswith("serving"))},
    }
    counts: Dict[str, int] = {}
    for s in sections:
        counts[s["status"]] = counts.get(s["status"], 0) + 1
    return {"sections": sections, "status_counts": counts, "audit": audit}


def build_perf_report(rows: Sequence[Mapping], *,
                      chip: Optional[str] = None) -> dict:
    """The ``obs perf`` report over bench rows (typically the banked
    history): per-op efficiency, bound classification, worst offenders
    by (pct-below-roofline x time share), waste attribution, per-phase
    serving MFU, and the recomputed VERDICT headline fractions."""
    default_spec = hwspec.spec(chip) if chip else None
    attributed, implausible = _attributed_rows(rows, default_spec)

    groups: Dict[str, List[dict]] = {}
    for a in attributed:
        groups.setdefault(a["group"], []).append(a)

    total_time = sum(a["seconds"] for a in attributed) or 1.0
    ops = []
    for name in sorted(groups):
        g = groups[name]
        pcts = sorted(a["res"].pct_roofline for a in g)
        effs = sorted(a["res"].effective_pct_roofline for a in g)
        best = max(g, key=lambda a: a["res"].pct_roofline)
        share = sum(a["seconds"] for a in g) / total_time
        ops.append({
            "op": name, "rows": len(g),
            "bound": best["res"].bound,
            "chip": best["res"].chip, "dtype": best["res"].dtype,
            "intensity": round(best["res"].intensity, 2),
            "pct_roofline": {
                "median": round(pcts[len(pcts) // 2], 4),
                "best": round(pcts[-1], 4)},
            "effective_pct_roofline": {
                "median": round(effs[len(effs) // 2], 4),
                "best": round(effs[-1], 4)},
            "best_achieved": {
                "tflops": round(best["res"].achieved_tflops, 2),
                "tbps": round(best["res"].achieved_tbps, 4)},
            "time_share": round(share, 4),
        })

    # worst offenders: how much of the measured time budget is lost to
    # running below roofline — (1 - best pct) x time share, the ranking
    # the VERDICT derived by hand for "make prefill fast" / "fix MLA"
    offenders = sorted(
        ({"op": o["op"], "bound": o["bound"],
          "pct_below_roofline": round(1.0 - o["pct_roofline"]["best"], 4),
          "time_share": o["time_share"],
          "severity": round((1.0 - o["pct_roofline"]["best"])
                            * o["time_share"], 4)}
         for o in ops if o["pct_roofline"]["best"] < 1.0),
        key=lambda d: -d["severity"])

    # padding/pruning waste: launched-vs-effective on rows that carry
    # the fused-prefill stats (new rows) — the packing attribution
    waste = []
    for a in attributed:
        c = a["cost"]
        if c.flops_effective is not None and c.flops > 0 \
                and c.flops_effective < c.flops:
            waste.append({
                "op": a["group"],
                "launched_flops": c.flops,
                "effective_flops": c.flops_effective,
                "waste_pct": round(
                    100.0 * (1.0 - c.flops_effective / c.flops), 2),
            })

    # serving-loop per-phase MFU: join the e2e row's measured
    # overhead_decomposition with the phase cost model
    serving = []
    for a in attributed:
        row = a["row"]
        if row.get("mode") != "e2e_measured":
            continue
        decomp = row.get("overhead_decomposition") or {}
        shape = costmodel.SERVING_SHAPES.get(str(row.get("model", "")))
        if not decomp or shape is None:
            continue
        phase_costs = costmodel.serving_phase_costs(
            int(row["bs"]), int(row["ctx"]), int(row["layers"]), **shape)
        spec = spec_for_row(row, default_spec)
        phases = {}
        for name, cost in phase_costs.items():
            us = decomp.get(name + "_us")
            if isinstance(us, (int, float)) and us > 0:
                res = attribute(cost, us * 1e-6, spec)
                phases[name] = {
                    "us": us, "bound": res.bound,
                    "mfu": round(res.mfu, 4),
                    "pct_roofline": round(res.pct_roofline, 4)}
        serving.append({
            "model": row.get("model"), "bs": row.get("bs"),
            "ctx": row.get("ctx"), "layers": row.get("layers"),
            "residual_us": decomp.get("residual_us"),
            "phases": phases,
        })

    return {
        "schema": "flashinfer_tpu.obs.perf/6",
        "chips": {name: dataclasses.asdict(s)
                  for name, s in sorted(hwspec.CHIP_SPECS.items())
                  if any(a["res"].chip == name for a in attributed)},
        "rows_total": len(rows),
        "rows_attributed": len(attributed),
        "rows_implausible": implausible,
        "ops": ops,
        "worst_offenders": offenders,
        "waste": waste,
        "serving_phase_mfu": serving,
        # the ICI dimension (perf/2): model-predicted, so it exists
        # before any multi-chip hardware does — per-phase collective
        # attribution at the canonical sharded cell + the tp scaling
        # curve per chip generation
        "serving_ici": predict_serving_ici(**SCALING_CELL),
        "scaling_prediction": predict_serving_scaling(**SCALING_CELL),
        # the tiered-KV dimension (perf/3): predicted per-request
        # kv_migrate wire cost + the measured migration stamps of
        # banked serving_disagg rows, joined
        "serving_disagg": _serving_disagg(attributed),
        # the prefill-ingest dimension (perf/4): predicted separate-vs-
        # fused byte drop at the headline prefill cells + the banked
        # ingest A/B rows, joined (ISSUE 14)
        "prefill_ingest": _prefill_ingest(attributed),
        # the host-loop dimension (perf/5): step-loop flight-deck
        # host-gap decomposition + the Amdahl projection, from banked
        # host_frac stamps and (when present) the live steploop ledger
        "host_loop": _host_loop(attributed),
        # the graduation dimension (perf/6): per tuning-config section,
        # pending | measured | quarantined in the hardware bring-up
        # pipeline, plus the predicted-vs-measured audit join of the
        # perf/2-perf/4 prediction families against banked phases
        "graduation": _graduation(attributed),
        "headline": _headline(attributed),
    }


def render_perf_report(report: Mapping) -> str:
    """Human rendering of :func:`build_perf_report` output."""
    lines: List[str] = []
    lines.append(f"# roofline attribution — "
                 f"{report['rows_attributed']}/{report['rows_total']} "
                 f"rows attributed")
    if report.get("rows_implausible"):
        lines.append(f"# {report['rows_implausible']} row(s) dropped: "
                     f"measured above the hardware ceiling (timer "
                     f"artifacts)")
    for name, s in report.get("chips", {}).items():
        lines.append(
            f"# chip {name}: {s['hbm_tbps']} TB/s HBM, "
            f"{s['mxu_tflops']['bf16']:g} bf16 / "
            f"{s['mxu_tflops']['int8']:g} int8 TFLOP/s")
    lines.append("")
    lines.append(f"{'op':38s} {'bound':7s} {'pct_roof':>9s} "
                 f"{'eff_pct':>8s} {'t_share':>8s}  best achieved")
    for o in report["ops"]:
        ach = o["best_achieved"]
        a = (f"{ach['tbps']:.3f} TB/s" if o["bound"] == "memory"
             else f"{ach['tflops']:.1f} TFLOP/s ({o['dtype']})")
        lines.append(
            f"{o['op'][:38]:38s} {o['bound']:7s} "
            f"{o['pct_roofline']['best']:9.3f} "
            f"{o['effective_pct_roofline']['best']:8.3f} "
            f"{o['time_share']:8.3f}  {a}")
    if report["worst_offenders"]:
        lines.append("")
        lines.append("worst offenders (pct-below-roofline x time share):")
        for w in report["worst_offenders"][:8]:
            lines.append(
                f"  {w['op'][:40]:40s} severity {w['severity']:.4f} "
                f"({w['pct_below_roofline']:.0%} below, "
                f"{w['time_share']:.1%} of time, {w['bound']}-bound)")
    if report["waste"]:
        lines.append("")
        lines.append("padding/pruning waste (launched vs effective):")
        for w in report["waste"][:8]:
            lines.append(f"  {w['op'][:40]:40s} {w['waste_pct']:.1f}% "
                         f"of launched FLOPs were padding")
    for s in report["serving_phase_mfu"]:
        lines.append("")
        lines.append(f"serving phase MFU ({s['model']} bs={s['bs']} "
                     f"ctx={s['ctx']} L={s['layers']}, residual "
                     f"{s['residual_us']} us):")
        for name, p in s["phases"].items():
            lines.append(f"  {name:12s} {p['us']:10.1f} us  "
                         f"mfu {p['mfu']:.3f}  "
                         f"pct_roofline {p['pct_roofline']:.3f} "
                         f"({p['bound']})")
    si = report.get("serving_ici")
    if si and si.get("phases"):
        lines.append("")
        lines.append(
            f"predicted serving collectives ({si['model']} bs={si['bs']} "
            f"ctx={si['ctx']} L={si['layers']}, {si['mesh_axes']}):")
        for name, p in si["phases"].items():
            per_chip = "  ".join(f"{c} {us:.1f} us"
                                 for c, us in p["pred_ici_us"].items())
            lines.append(f"  {name:12s} {p['ici_bytes'] / 1e6:10.2f} MB "
                         f"ICI/step  {per_chip}")
    sd = report.get("serving_disagg")
    if sd:
        p = sd["predicted_kv_migrate"]
        per_chip = "  ".join(f"{c} {us:.1f} us"
                             for c, us in p["pred_ici_us"].items())
        lines.append("")
        lines.append(
            f"predicted kv_migrate handoff ({p['model']} ctx={p['ctx']} "
            f"L={p['layers']}): "
            f"{p['ici_bytes_per_request'] / 1e6:.2f} MB/request  "
            f"{per_chip}")
        for m in sd.get("rows", []):
            ratio = m.get("measured_vs_pred_wire")
            lines.append(
                f"  measured {m.get('mode', '?'):10s} "
                f"{m.get('migrations', 0):5d} migrations, "
                f"{float(m.get('migrate_bytes', 0)) / 1e6:10.2f} MB"
                + (f"  {ratio:.2f}x pred wire" if ratio else ""))
    pi = report.get("prefill_ingest")
    if pi:
        lines.append("")
        lines.append("predicted prefill-ingest byte drop (separate-op "
                     "vs fused, headline cells):")
        for name, cell in pi["predicted"].items():
            chips = "  ".join(
                f"{c} {'ON' if v['use_fused'] else 'off'}"
                for c, v in cell["chips"].items())
            lines.append(
                f"  {name:24s} {cell['separate_bytes'] / 1e6:9.1f} -> "
                f"{cell['fused_bytes'] / 1e6:9.1f} MB  "
                f"(-{cell['avoided_fraction']:.0%})  knob: {chips}")
        for m in pi.get("rows", []):
            lines.append(
                f"  measured {'fused ' if m.get('fused_ingest') else 'separate'}"
                f" {m.get('kind', '?')} qlen={m.get('qlen')}: "
                f"{m.get('us', 0):.1f} us"
                + (f"  ({float(m['ingest_bytes_avoided']) / 1e6:.1f} MB"
                   f" avoided pred)" if m.get("ingest_bytes_avoided")
                   else ""))
    hl = report.get("host_loop")
    if hl and (hl.get("rows") or hl.get("live")):
        lines.append("")
        lines.append("host loop (step-loop flight deck — Amdahl ceiling "
                     "= max speedup a perfect host/device pipeline buys):")
        for m in hl.get("rows", []):
            tag = m.get("mode") or m.get("variant") \
                or m.get("step_mode") or ""
            lines.append(
                f"  {m.get('phase', '?'):16s} {str(tag):12s} "
                f"host_frac {float(m['host_frac']):.3f}  "
                f"gap {float(m.get('host_gap_us', 0)):9.1f} us  "
                f"ceiling {m['amdahl_ceiling']:.2f}x"
                + (f"  pred/meas {float(m['pred_step_ratio']):.3f}"
                   if m.get("pred_step_ratio") is not None else ""))
        live = hl.get("live")
        if live:
            lines.append(
                f"  live ledger: {live['steps']} steps "
                f"({live['idle_ticks']} idle), host_frac "
                f"{live['host_frac']:.3f}, ceiling "
                f"{live['amdahl_ceiling']:.2f}x, worst sub-phase "
                f"{live['worst_phase']}")
    grad = report.get("graduation")
    if grad and grad.get("sections"):
        lines.append("")
        counts = grad.get("status_counts", {})
        lines.append(
            "graduation (hardware bring-up pipeline): "
            + "  ".join(f"{k} {v}" for k, v in sorted(counts.items())))
        for s in grad["sections"]:
            ref = ""
            if s["status"] == "measured":
                ref = f"  journal {s.get('journal_id', '?')}"
            lines.append(
                f"  {s['chip']:6s} {s['section']:16s} "
                f"{s['status']:11s} ({s['tactics']} tactic(s)){ref}")
        audit = grad.get("audit") or {}
        if audit:
            lines.append("  predicted-vs-measured audit join:")
            for fam, a in audit.items():
                lines.append(
                    f"    {fam:16s} {a['predicted_schema']:7s} "
                    f"measured rows: {a['measured_rows']}")
    sc = report.get("scaling_prediction")
    if sc:
        lines.append("")
        lines.append("predicted tp scaling (sharded serving step, "
                     "speedup vs tp1 / scaling efficiency):")
        for chip, rows in sc.items():
            cells = "  ".join(
                f"tp{tp}: {r['speedup_vs_tp1']:.2f}x/"
                f"{r['scaling_efficiency']:.2f}"
                for tp, r in rows.items())
            lines.append(f"  {chip}: {cells}")
    h = report.get("headline", {})
    if h:
        lines.append("")
        lines.append("headline fractions (the round-5 VERDICT numbers, "
                     "recomputed):")
        for key, rng in h.items():
            lines.append(f"  {key}: {rng['min']:.3f} - {rng['max']:.3f}")
    return "\n".join(lines) + "\n"
