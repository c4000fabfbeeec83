"""Split-factor sweep harness for the split-KV paged decode path.

The on-chip autotune surface for ISSUE 6's tentpole (c): sweeps the
``decode.splits`` knob — the per-request split-KV partition factor —
across the short-context/large-batch decode shape grid (the round-5
VERDICT's 0.21–0.54 TB/s cliff cells plus the long-context control
rows), emits ``ROW {json}`` lines, and quality-stamps every row through
``obs.bench_audit.RowAuditor`` against the BENCH_BANKED.md history (the
same <0.35x implausibility rule as bench.py).

Rows are roofline-stamped by the shared cost model
(``obs.costmodel.decode_split``) with the split metadata fields
(``num_splits``, ``merge_bytes`` — docs/observability.md), and
candidates are RANKED on ``effective_pct_roofline`` — the fraction of
the binding roofline counting only useful work, so a candidate can't
win by streaming masked chunk tails or writing padded partials.

Usage::

    python benchmarks/bench_decode_splits.py            # on-chip sweep
    python benchmarks/bench_decode_splits.py --smoke    # CPU interpret
    python benchmarks/bench_decode_splits.py --emit-config > decode.json

``--emit-config`` prints a ready-to-paste ``"decode"`` section for
``flashinfer_tpu/tuning_configs/<gen>.json`` with each shape's winner —
the step that graduates the shipped section from ``"seed": true``
(cost-model-derived) to measured (docs/performance.md walks the
workflow).  Each shape also prints the cost model's own predicted
ranking next to the measured one, so every banked run doubles as a
predicted-vs-measured check on the split chooser.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # runnable from any cwd (sys.path[0] is benchmarks/)
    sys.path.insert(0, _REPO)

_AUDITOR = None

SPLIT_CANDIDATES = (1, 2, 4, 8)


def _emit_row(**kw):
    """One measurement, RowAuditor-stamped, parseable by orchestrators."""
    global _AUDITOR
    try:
        from flashinfer_tpu.obs import bench_audit

        if _AUDITOR is None:
            _AUDITOR = bench_audit.RowAuditor(
                bench_audit.load_banked_history(
                    os.path.join(_REPO, "BENCH_BANKED.md")))
        _AUDITOR.stamp(kw)
    except Exception as e:  # noqa: BLE001 - the audit must never cost a row
        print(f"# row audit failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    print("ROW " + json.dumps(kw), flush=True)
    return kw


def shape_grid(smoke: bool):
    """(bs, ctx, HQ, HKV, D, PS) sweep shapes: the VERDICT short-context
    cliff cells first (bs=256/ctx=512 is the headline target), then
    long-context controls where the cost model predicts S=1 must win."""
    if smoke:
        return [(4, 128, 8, 2, 64, 16)]
    return [
        (256, 512, 32, 8, 128, 16),   # the 0.21-0.54 TB/s cliff cell
        (64, 512, 32, 8, 128, 16),
        (16, 2048, 32, 8, 128, 16),
        (64, 4096, 32, 8, 128, 16),   # long-context control: S=1 expected
    ]


def sweep(smoke: bool, repeats: int):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.paged_decode import (
        build_decode_split_units, paged_decode_attention,
        paged_decode_attention_split, split_pages_per_chunk,
    )
    from flashinfer_tpu.obs import costmodel, hwspec, roofline
    from flashinfer_tpu.testing import bench_fn_device
    from flashinfer_tpu import compile_guard

    chip = hwspec.current_spec()

    winners = {}
    for bs, ctx, HQ, HKV, D, PS in shape_grid(smoke):
        ppr = -(-ctx // PS)
        npages = bs * ppr
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        kc = jax.random.normal(key, (npages, HKV, PS, D), jnp.bfloat16)
        vc = jax.random.normal(jax.random.fold_in(key, 1),
                               (npages, HKV, PS, D), jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (bs, HQ, D), jnp.bfloat16)
        table = rng.permutation(npages).astype(np.int32).reshape(bs, ppr)
        kv_lens = np.full((bs,), ctx, np.int64)
        ppc = split_pages_per_chunk(PS, HKV, D, 2)
        shape_key = "_".join(map(str, (
            bs, ppr, HQ, HKV, D, PS, ppc, "bfloat16")))

        # the chooser's own prediction, printed next to the measurement
        # (the predicted-vs-measured loop ROADMAP item 5 asks for)
        pred_best, pred = costmodel.choose_decode_splits(
            bs, ctx, HQ, HKV, D, hbm_tbps=chip.hbm_tbps, page_size=PS,
            pages_per_chunk=ppc)

        best = None
        for S in SPLIT_CANDIDATES:
            if S == 1:
                pt = jnp.asarray(table)
                lens = jnp.asarray(kv_lens.astype(np.int32))

                def thunk(qq, kk, vv, pt=pt, lens=lens):
                    return paged_decode_attention(
                        qq, kk, vv, pt, lens, sm_scale=D ** -0.5,
                        kv_layout="HND")
            else:
                plan_np = build_decode_split_units(
                    table, kv_lens, num_splits=S, page_size=PS,
                    pages_per_chunk=ppc)
                statics = dict(
                    num_units=plan_np.pop("num_units"),
                    num_splits=plan_np.pop("num_splits"),
                    single_chunk=plan_np.pop("single_chunk"),
                    pages_per_chunk=plan_np.pop("pages_per_chunk"),
                )
                plan_np.pop("stats")
                plan = {k: jnp.asarray(v) for k, v in plan_np.items()}

                def thunk(qq, kk, vv, plan=plan, statics=statics):
                    return paged_decode_attention_split(
                        qq, kk, vv, plan, sm_scale=D ** -0.5, **statics)
            try:
                t = compile_guard.guarded(
                    "bench.decode_splits",
                    (bs, ctx, HQ, HKV, D, PS, ppc, S),
                    lambda: bench_fn_device(thunk, q, kc, vc,
                                            repeats=repeats),
                )
            except Exception as e:  # noqa: BLE001 - one cell, not the sweep
                first = (str(e).splitlines() or ["?"])[0][:120]
                print(f"# splits S={S} FAILED {type(e).__name__}: "
                      f"{first}", file=sys.stderr)
                continue
            bd = costmodel.decode_split_breakdown(
                bs, ctx, HQ, HKV, D, num_splits=S, page_size=PS,
                pages_per_chunk=ppc)
            cost = costmodel.decode_split(
                bs, ctx, HQ, HKV, D, num_splits=S, page_size=PS,
                pages_per_chunk=ppc)
            tbps = cost.bytes_total / t / 1e12
            row = _emit_row(**roofline.stamp_row(
                dict(phase="decode_splits", bs=bs, ctx=ctx,
                     us=round(t * 1e6, 1), tbps=round(tbps, 4),
                     pred_us=round(pred.get(S, {}).get(
                         "seconds", 0.0) * 1e6, 1)),
                cost, t, chip, num_splits=S,
                merge_bytes=bd["merge_bytes"]))
            eff = row["effective_pct_roofline"]
            print(f"# splits bs={bs:4d} ctx={ctx:5d} S={S}: "
                  f"{t*1e6:9.1f} us  {tbps:6.4f} TB/s  "
                  f"eff_roof {eff:6.3f}  [{row.get('quality', '?')}]",
                  file=sys.stderr)
            if row.get("quality") != "poison" and (
                    best is None or eff > best[0]):
                best = (eff, S)
        if best is not None:
            winners[f"decode.splits|{shape_key}"] = best[1]
            agree = "agrees" if best[1] == pred_best else "DISAGREES"
            print(f"# winner bs={bs} ctx={ctx}: S={best[1]} "
                  f"(cost model predicted S={pred_best} — {agree})",
                  file=sys.stderr)
    return winners


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, interpret-safe (CPU CI)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--emit-config", action="store_true",
                    help="print a tuning_configs 'decode' section with "
                         "each shape's winner on stdout")
    args = ap.parse_args()
    winners = sweep(args.smoke, args.repeats)
    if args.emit_config:
        print(json.dumps({"decode": {
            "comment": "measured by benchmarks/bench_decode_splits.py "
                       "(replace the shipped seed section with this)",
            "seed": bool(args.smoke),
            "tactics": winners,
        }}, indent=1))
    else:
        print(json.dumps({"winners": winners}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
