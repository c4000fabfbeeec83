"""Self-auditing bench telemetry: machine-stamped row quality.

VERDICT weak #3: the banked bench record contains poison rows
(degraded-window artifacts reading ~19x low) that were only caught by
manual cross-checking, and the in-phase ``<0.35x best`` re-measure
guard had never executed.  This module moves the audit to EMIT time:
a row is routed through :class:`RowAuditor` (its one emitter,
``bench.py``, is deleted: ROADMAP C3 decides this module), which
compares the row's primary throughput metric against the best known
measurement of the same configuration — across this run AND the
``BENCH_BANKED.md`` history — and stamps::

    quality: "ok"        >= 0.70x best   (normal run-to-run spread;
                                          banked dispersion is ~2x
                                          across the grid, ~4% at a
                                          fixed cell)
    quality: "degraded"  [0.35x, 0.70x)  (suspicious window; keep but
                                          don't bank as the cell's
                                          number without a re-measure)
    quality: "poison"    < 0.35x best    (the committed implausibility
                                          rule from phase_decode —
                                          never quote this row)

plus ``vs_best`` (the ratio) so the stamp is auditable.  Rows with no
comparable history are ``ok`` by definition (best = self).

The key is the row's full non-measurement identity (phase + every
config field), so a bs=64 ctx=4096 decode row only ever competes with
other bs=64 ctx=4096 decode rows.
"""

from __future__ import annotations

import hashlib
import json
import re
from typing import Dict, Iterable, List, Optional, Tuple

# measurement outputs (never part of a row's identity key).  The
# roofline stamp fields (obs.roofline.ROW_FIELDS) live here too — and
# so does ``chip``: a bs=64 ctx=4096 decode row measured on v5p MUST
# compete with the v5e history for the same configuration, which is
# exactly what the roofline-fraction comparison below makes fair.
MEASUREMENT_FIELDS = frozenset({
    "us", "tbps", "tok_s", "tflops", "gbps", "pct_roofline",
    "kernel_us", "xla_us", "speedup", "us_per_layer", "us_step_80l",
    "tok_s_per_chip", "linearity", "us_step", "tok_s_at_depth",
    "slope_pred_us", "overhead_vs_slope", "overhead_decomposition",
    "peak", "quality", "vs_best", "vs_best_roofline",
    "flops", "bytes_read", "bytes_written", "intensity", "bound",
    "effective_pct_roofline", "chip", "dtype", "flops_effective",
    # split-KV decode stamp: merge_bytes is derived from the cost model
    # and pred_us from its predictor (both recalibrate-able — like
    # slope_pred_us, never identity); num_splits is deliberately NOT
    # here — rows at different split factors are different
    # configurations and must not compete in the quality audit
    "merge_bytes", "pred_us",
    # serving_fused A/B: the per-step host-dispatch residual (us_step
    # minus the shared slope floor) — derived, never identity.
    # step_mode (fused | per_op) is deliberately NOT here: the two
    # dispatch structures are different configurations with separate
    # banked histories, the num_splits precedent
    "dispatch_residual_us",
    # sharded serving step: predicted ICI wire bytes + the fraction of
    # measured time the ICI floor explains (both derived from the cost
    # model — recalibrate-able, never identity).  mesh_axes
    # (ShardingPlan.mesh_axes, e.g. "dp1.tp8") is deliberately NOT
    # here: mesh SHAPE is configuration, so a tp8 row never competes
    # with tp1 history — the step_mode/num_splits precedent
    "ici_bytes", "pct_ici_roofline",
    # request-lifecycle stamps on serving rows (ISSUE 10): steady-state
    # time-per-output-token and first-step-from-fresh-state latency —
    # measurements of the same run, never identity
    "tpot_us", "ttft_us",
    # continuous-batching engine rows (serving_engine phase): the
    # measured prefix-cache hit rate, the cost-model-priced prefill
    # FLOPs the hits avoided, and the run's compile/retrace/preempt/
    # evict outcomes — all measurements of the same workload replay
    # (the Zipf skew + request mix ARE identity and stay so)
    "prefix_hit_rate", "prefill_flops_avoided", "num_traces",
    "preemptions", "evictions",
    "ttft_p50_us", "ttft_p99_us", "tpot_p50_us", "tpot_p99_us",
    # attention_backend ("reference" — the dense XLA oracle tier — vs
    # "kernel" — the Pallas work-unit lowering) is deliberately NOT
    # here: the two attention tiers are different configurations with
    # separate banked histories even at identical engine shapes, so a
    # kernel-tier row never competes with reference-row history — the
    # step_mode/mesh_axes precedent (roofline.stamp_row stamps it)
    # backend-token agreement of the serving_engine A/B pair — derived
    # cross-row check results, never identity (exact on f32 models,
    # rate-reported on bf16 where the kernel tier's bf16 MXU dots
    # legitimately round differently from the f32-upcast reference)
    "backend_tokens_equal", "backend_token_match",
    # tiered-KV rows (serving_disagg phase): migration/spill/restore
    # traffic, host-copy wall time, and the resume-miss count — all
    # measurements of the same workload replay.  ``mode``
    # (handoff | kv_migrate | spill) is deliberately NOT here: the
    # three tier exercises are different configurations with separate
    # banked histories (the step_mode/mesh_axes precedent), and so is
    # the engine/pool geometry that shapes them
    "migrations", "migrate_bytes", "migrate_us", "unified_wall_s",
    "spills", "restores", "spill_bytes", "restore_bytes",
    "recomputes", "host_evictions", "disagg_tokens_equal",
    "spill_tokens_equal",
    # prefill ingest A/B (ISSUE 14): the cost model's predicted
    # avoided-HBM delta for the row's shape — derived like merge_bytes,
    # never identity.  ``fused_ingest`` (the ingest-mode flag itself)
    # is deliberately NOT here: fused and separate rows of the A/B
    # pair are different configurations with separate banked histories
    # (the step_mode/attention_backend precedent;
    # roofline.stamp_row stamps it)
    "ingest_bytes_avoided",
    # step-loop flight-deck stamps on serving rows (ISSUE 17): device
    # idle per step, the host-serialization fraction of the cadence,
    # and the cost model's predicted/measured step-time ratio — all
    # measurements of the same run (the tpot_us/ttft_us precedent),
    # never identity; perf/6's host_loop section joins on them
    "host_gap_us", "host_frac", "pred_step_ratio",
    # configuration-identity digest (ISSUE 20): sha256[:12] of row_key,
    # stamped by RowAuditor so bring-up journal entries and graduated
    # tuning sections can reference banked rows; derived from identity,
    # never part of it (and recomputable for pre-stamp history rows)
    "row_id",
})

# primary throughput metric, in preference order; all higher-is-better
THROUGHPUT_FIELDS = ("tbps", "tflops", "gbps", "tok_s_per_chip",
                     "tok_s_at_depth", "tok_s", "speedup")

POISON_THRESHOLD = 0.35  # the committed phase_decode implausibility rule
DEGRADED_THRESHOLD = 0.70
# a measurement above the binding hardware ceiling is a timer artifact
# (the <0.35x rule only catches too-SLOW artifacts; the banked history
# carries decode rows at 1.5-2.0x the v5e roofline from slope-fit noise
# on ~20 us kernels) — small tolerance for spec rounding
IMPLAUSIBLY_FAST_ROOFLINE = 1.05

_JSON_BLOCK_RE = re.compile(r"^```json\s*$(.*?)^```\s*$",
                            re.MULTILINE | re.DOTALL)


def row_key(row: dict) -> Tuple:
    """Hashable identity of a row's configuration."""
    return tuple(sorted(
        (k, str(v)) for k, v in row.items()
        if k not in MEASUREMENT_FIELDS
    ))


def row_stamp(row: dict) -> str:
    """12-hex configuration-identity digest (sha256 of :func:`row_key`).

    The join key between the bring-up session journal / graduated tuning
    sections and banked rows: rows of the same configuration share a
    stamp across runs, and the stamp is recomputable for history rows
    banked before RowAuditor started writing ``row_id``."""
    key = json.dumps(row_key(row))
    return hashlib.sha256(key.encode()).hexdigest()[:12]


# fields obs.roofline.stamp_row always writes alongside pct_roofline —
# their presence identifies a stamped (fraction-valued) row
_STAMP_MARKERS = ("bound", "chip", "flops")


def roofline_fraction(row: dict) -> Optional[float]:
    """The row's fraction-of-binding-roofline, normalized.  Rows
    stamped by obs.roofline (identified by the stamp fields riding
    along) carry a 0..1 fraction; pre-roofline scans rows banked a
    PERCENT under the same name, and the banked history spans 0.5-94.0
    percent — magnitude can't discriminate (a 0.6-percent artifact row
    would read as a winning 0.6 fraction), the stamp's presence can."""
    v = row.get("pct_roofline")
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        return None
    v = float(v)
    if any(row.get(k) is not None for k in _STAMP_MARKERS):
        return v
    return v / 100.0


def primary_metric(row: dict) -> Optional[Tuple[str, float]]:
    """(field, higher-is-better value) or None if the row carries no
    recognized throughput number (latency-only rows fall back to 1/us)."""
    for f in THROUGHPUT_FIELDS:
        v = row.get(f)
        if isinstance(v, (int, float)) and v > 0:
            return f, float(v)
    v = row.get("us") or row.get("us_step") or row.get("kernel_us")
    if isinstance(v, (int, float)) and v > 0:
        return "inv_us", 1.0 / float(v)
    return None


def load_banked_history(path: str, strict: bool = False) -> List[dict]:
    """Rows from every ```json block of a BENCH_BANKED.md-style file
    (each block is a full run record with a "rows" list).  Tolerant by
    default: a malformed block is skipped, an absent file is empty
    history.  ``strict=True`` (the ``obs perf`` CI smoke gate) raises
    ``ValueError`` naming every malformed block / non-dict row instead
    of silently dropping data."""
    rows: List[dict] = []
    errors: List[str] = []
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError as e:
        if strict:
            raise ValueError(f"{path}: {e}") from e
        return rows
    for m in _JSON_BLOCK_RE.finditer(text):
        line = text.count("\n", 0, m.start()) + 1
        try:
            record = json.loads(m.group(1))
        except json.JSONDecodeError as e:
            errors.append(f"{path}:{line}: malformed json block ({e})")
            continue
        got = record.get("rows", []) if isinstance(record, dict) else []
        bad = sum(1 for r in got if not isinstance(r, dict))
        if bad:
            errors.append(f"{path}:{line}: {bad} non-dict row(s)")
        rows.extend(r for r in got if isinstance(r, dict))
    if strict and errors:
        raise ValueError("; ".join(errors))
    return rows


class RowAuditor:
    """Tracks best-by-configuration and stamps rows in place.

    Two comparison spaces per configuration key:

    - **raw** (the original rule): the primary throughput metric vs the
      best known raw measurement — meaningful when history and row come
      from the same chip generation;
    - **roofline-fraction** (chip-generation-portable): the row's
      ``pct_roofline`` vs the best known fraction for the key.  ``chip``
      is a measurement field, so a v5p row and the v5e history share a
      key — raw TB/s would mis-compare across that boundary in either
      direction, while fraction-of-own-roofline stays honest.  When
      both spaces are available the fraction ratio decides the quality
      stamp; the raw ratio still rides along as ``vs_best``.
    """

    def __init__(self, history: Iterable[dict] = ()):
        self._best: Dict[Tuple, float] = {}
        self._best_frac: Dict[Tuple, float] = {}
        for row in history:
            self._account(row)

    def _account(self, row: dict) -> None:
        # a row some past auditor already stamped poison never defines
        # the baseline.  Low artifacts can't raise the max() anyway;
        # this guards the residual case — history trimmed down to a
        # lone flagged row for a key (pre-stamping banked rows carry no
        # quality field and are accounted normally)
        if row.get("quality") == "poison":
            return
        key = row_key(row)
        pm = primary_metric(row)
        if pm is not None:
            _, value = pm
            if value > self._best.get(key, 0.0):
                self._best[key] = value
        frac = roofline_fraction(row)
        if frac is not None and frac > self._best_frac.get(key, 0.0):
            self._best_frac[key] = frac

    def stamp(self, row: dict) -> dict:
        """Add ``quality`` (+ ``vs_best`` / ``vs_best_roofline`` when
        history exists) to `row` in place and fold it into the running
        best.  Never raises."""
        try:
            key = row_key(row)
            row["row_id"] = row_stamp(row)
            pm = primary_metric(row)
            ratio_raw = None
            if pm is not None:
                _, value = pm
                best = max(self._best.get(key, 0.0), value)
                ratio_raw = value / best
                if best > value:
                    row["vs_best"] = round(ratio_raw, 3)
            ratio_frac = None
            frac = roofline_fraction(row)
            if frac is not None and frac > IMPLAUSIBLY_FAST_ROOFLINE:
                # faster than the hardware ceiling: a timer artifact,
                # poisoned outright (and never folded into the best)
                row["quality"] = "poison"
                return row
            if frac is not None:
                best_frac = max(self._best_frac.get(key, 0.0), frac)
                ratio_frac = frac / best_frac
                if best_frac > frac:
                    row["vs_best_roofline"] = round(ratio_frac, 3)
            # fraction space takes precedence: it is the comparison
            # that stays valid when the chip generation changed
            ratio = ratio_frac if ratio_frac is not None else ratio_raw
            if ratio is None:
                row["quality"] = "ok"  # nothing measurable to audit
                return row
            if ratio < POISON_THRESHOLD:
                row["quality"] = "poison"
            elif ratio < DEGRADED_THRESHOLD:
                row["quality"] = "degraded"
            else:
                row["quality"] = "ok"
            self._account(row)
        except Exception:  # noqa: BLE001 - the audit must never cost a row
            row.pop("quality", None)
        return row
