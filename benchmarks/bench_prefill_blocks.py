"""Block-shape sweep harness for the pipelined fused prefill kernel.

The on-chip autotune surface for ISSUE 3's tentpole (c): sweeps the
``fused_prefill.blocks`` knob — (qo-tile ``block_q``, kv-chunk
``pages_per_chunk``) — across the paged chunked-prefill shape grid,
emits ``ROW {json}`` lines carrying the full block-config metadata, and
quality-stamps every row through ``obs.bench_audit.RowAuditor`` against
the BENCH_BANKED.md history (the same <0.35x implausibility rule as
bench.py).  Rows are roofline-stamped by the shared cost model from
each candidate's own plan stats, so ``tflops`` (and the ranking) count
*effective* work — a candidate can't win by padding — while
``pct_roofline`` vs ``effective_pct_roofline`` shows the waste
(docs/observability.md §"Roofline attribution").

Usage::

    python benchmarks/bench_prefill_blocks.py            # on-chip sweep
    python benchmarks/bench_prefill_blocks.py --smoke    # CPU interpret
    python benchmarks/bench_prefill_blocks.py --emit-config > prefill.json

``--emit-config`` prints a ready-to-paste ``"prefill"`` section for
``flashinfer_tpu/tuning_configs/<gen>.json`` with each shape's winner —
the step that graduates the shipped section from ``"seed": true`` to
measured (docs/performance.md walks the workflow).

Candidate ceiling note: chunk_tokens stays <= 256 (ppc <= 16 at page 16)
— each work unit unrolls 2 DMAs/page and 32 in-flight copies is the
on-chip-validated queue ceiling; ppc=32 would be the W002 queue-unroll
wedge class (see ops/paged_prefill.py kv_dmas).
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


def candidate_grid(page_size: int, smoke: bool):
    """(block_q, pages_per_chunk) candidates — the SAME grid the
    wrapper's in-run tuner explores (ops/paged_prefill.block_candidates,
    W002-safe chunk ceiling), so banked winners are always reproducible
    by runtime autotune."""
    if smoke:
        return [(32, 2), (64, 2), (64, 4)]
    from flashinfer_tpu.ops.paged_prefill import block_candidates

    return block_candidates(page_size)


def shape_grid(smoke: bool):
    """(bs, qlen, ctx, HQ, HKV, D, page_size) sweep shapes — the bench.py
    prefill phase configs plus the VERDICT next-round target cell."""
    if smoke:
        return [(2, 32, 64, 4, 2, 64, 8)]
    return [
        (8, 512, 4096, 32, 8, 128, 16),   # VERDICT target: >= 60 TFLOPS
        (2, 2048, 8192, 32, 8, 128, 16),
        (16, 256, 2048, 32, 8, 128, 16),
    ]


def sweep(smoke: bool, repeats: int):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_work_units, fused_paged_prefill,
    )
    from flashinfer_tpu.obs import costmodel, hwspec, roofline
    from flashinfer_tpu.testing import bench_fn_device
    from flashinfer_tpu import compile_guard

    chip = hwspec.current_spec()

    winners = {}
    for bs, qlen, ctx, HQ, HKV, D, PS in shape_grid(smoke):
        ppr = ctx // PS
        npages = bs * ppr
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        kc = jax.random.normal(key, (npages, HKV, PS, D), jnp.bfloat16)
        vc = jax.random.normal(jax.random.fold_in(key, 1),
                               (npages, HKV, PS, D), jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (bs * qlen, HQ, D), jnp.bfloat16)
        qo_indptr = np.arange(bs + 1, dtype=np.int32) * qlen
        kv_page_indptr = np.arange(bs + 1, dtype=np.int32) * ppr
        kv_page_indices = rng.permutation(npages).astype(np.int32)
        kv_lens = np.full((bs,), ctx, np.int64)
        fused_key = "_".join(map(str, (
            bs, max(1 << (bs * qlen - 1).bit_length(), 128), HQ, HKV, D, PS,
        )))

        best = None
        for bq, ppc in candidate_grid(PS, smoke):
            plan_np = build_prefill_work_units(
                qo_indptr, kv_page_indptr, kv_page_indices, kv_lens,
                block_q=bq, pages_per_chunk=ppc, page_size=PS, causal=True,
            )
            statics = dict(
                num_units=plan_np.pop("num_units"),
                block_q=plan_np.pop("block_q"),
                pages_per_chunk=plan_np.pop("pages_per_chunk"),
            )
            stats = plan_np.pop("stats")
            plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
            try:
                t = compile_guard.guarded(
                    "bench.prefill_blocks",
                    (bs, qlen, ctx, HQ, HKV, D, PS, bq, ppc),
                    lambda: bench_fn_device(
                        lambda qq, kk, vv: fused_paged_prefill(
                            qq, kk, vv, plan, sm_scale=D ** -0.5,
                            causal=True, **statics),
                        q, kc, vc, repeats=repeats,
                    ),
                )
            except Exception as e:  # noqa: BLE001 - one cell, not the sweep
                first = (str(e).splitlines() or ["?"])[0][:120]
                print(f"# blocks ({bq},{ppc}) FAILED "
                      f"{type(e).__name__}: {first}", file=sys.stderr)
                continue
            # shared cost model: launched work from THIS candidate's
            # plan stats, effective = attended tokens — `tflops` stays
            # the effective number so candidates with different padding
            # waste compare on useful work (and the stamped
            # effective_pct_roofline ranks them the same way)
            cost = costmodel.paged_prefill(
                bs, qlen, ctx, HQ, HKV, D, causal=True, stats=stats,
                block_q=bq, pages_per_chunk=ppc, page_size=PS)
            tflops = cost.effective_flops / t / 1e12
            row = _emit_row(**roofline.stamp_row(
                dict(phase="prefill_blocks", bs=bs, qlen=qlen, ctx=ctx,
                     block_q=bq, pages_per_chunk=ppc,
                     num_units=statics["num_units"],
                     units_pruned=stats["units_pruned"],
                     us=round(t * 1e6, 1), tflops=round(tflops, 2)),
                cost, t, chip))
            print(f"# blocks bs={bs} qlen={qlen} ctx={ctx} "
                  f"bq={bq:3d} ppc={ppc:2d}: {t*1e6:9.1f} us  "
                  f"{tflops:6.2f} TFLOP/s  [{row.get('quality', '?')}]",
                  file=sys.stderr)
            if row.get("quality") != "poison" and (
                    best is None or tflops > best[0]):
                best = (tflops, bq, ppc)
        if best is not None:
            winners[f"fused_prefill.blocks|{fused_key}"] = [best[1], best[2]]
    return winners


def sweep_ingest(smoke: bool, repeats: int):
    """The fused-ingest A/B (ISSUE 14): for each sweep shape, time the
    fused RoPE+quantize-append+attention launch against the separate-op
    composition THROUGH THE SAME ``run_ingest`` entry point (the plan
    static flips the mode), emit paired rows carrying the
    ``fused_ingest`` identity stamp + the cost model's
    ``ingest_bytes_avoided`` measurement, and return per-shape
    ``prefill.fused_ingest`` winners for ``--emit-config``."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import flashinfer_tpu as fi
    from flashinfer_tpu.obs import costmodel, hwspec, roofline
    from flashinfer_tpu.testing import bench_fn_device

    if smoke:
        # the wrapper's fused work-unit path (run_ingest's requirement)
        # needs the pallas tier; interpret mode serves it off-chip
        os.environ.setdefault("FLASHINFER_TPU_BACKEND", "pallas")
    chip = hwspec.current_spec()
    winners = {}
    for bs, qlen, ctx, HQ, HKV, D, PS in shape_grid(smoke):
        ppr = ctx // PS
        npages = bs * ppr
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (bs * qlen, HQ, D), jnp.bfloat16)
        k_new = jax.random.normal(jax.random.fold_in(key, 1),
                                  (bs * ctx, HKV, D), jnp.bfloat16)
        v_new = jax.random.normal(jax.random.fold_in(key, 2),
                                  (bs * ctx, HKV, D), jnp.bfloat16)
        kc = jnp.zeros((npages, HKV, PS, D), jnp.bfloat16)
        vc = jnp.zeros((npages, HKV, PS, D), jnp.bfloat16)
        qo_indptr = np.arange(bs + 1, dtype=np.int32) * qlen
        kv_page_indptr = np.arange(bs + 1, dtype=np.int32) * ppr
        kv_page_indices = rng.permutation(npages).astype(np.int32)
        fused_key = "_".join(map(str, (
            bs, max(1 << (bs * qlen - 1).bit_length(), 128), HQ, HKV, D,
            PS)))
        bd = costmodel.prefill_ingest_breakdown(
            bs * qlen, bs * ctx, HQ, HKV, D)
        pair = {}
        for mode in (True, False):
            w = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="HND")
            w.plan(qo_indptr, kv_page_indptr, kv_page_indices,
                   np.full((bs,), PS, np.int32), HQ, HKV, D, PS,
                   causal=True, fused_ingest=mode)
            try:
                t = bench_fn_device(
                    lambda qq, kk, vv, kc_, vc_: w.run_ingest(
                        qq, kk, vv, (kc_, vc_)),
                    q, k_new, v_new, kc, vc, repeats=repeats)
            except Exception as e:  # noqa: BLE001 - one cell, not the sweep
                first = (str(e).splitlines() or ["?"])[0][:120]
                print(f"# ingest mode={mode} FAILED "
                      f"{type(e).__name__}: {first}", file=sys.stderr)
                continue
            if mode:
                cost = costmodel.prefill_ingest(
                    bs * qlen, bs * ctx, HQ, HKV, D,
                    stats=getattr(w, "_ingest_stats", None),
                    block_q=(w.fused_prefill_config or {}).get("block_q"),
                    pages_per_chunk=(w.fused_prefill_config or {}).get(
                        "pages_per_chunk"),
                    page_size=PS)
            else:
                # the separate row's wall covers rope + append +
                # attention: price the three-pass traffic (same op
                # family as the fused row), not attention alone
                cost = costmodel.prefill_ingest_separate(
                    bs * qlen, bs * ctx, HQ, HKV, D, causal=True)
            _emit_row(**roofline.stamp_row(
                dict(phase="prefill_blocks", kind="ingest_ab", bs=bs,
                     qlen=qlen, ctx=ctx, us=round(t * 1e6, 1),
                     tflops=round(cost.effective_flops / t / 1e12, 2)),
                cost, t, chip, fused_ingest=mode,
                ingest_bytes_avoided=bd["bytes_avoided"]))
            pair[mode] = t
            print(f"# ingest bs={bs} qlen={qlen} ctx={ctx} "
                  f"{'fused   ' if mode else 'separate'}: "
                  f"{t*1e6:9.1f} us  (pred avoided "
                  f"{bd['bytes_avoided']/1e6:.1f} MB)", file=sys.stderr)
        if True in pair and False in pair:
            win = pair[True] < pair[False] * 0.98
            winners[f"prefill.fused_ingest|{fused_key}"] = \
                "on" if win else "off"
            print(f"# ingest bs={bs} qlen={qlen} ctx={ctx} winner: "
                  f"{'fused' if win else 'separate'} "
                  f"({pair[False]/pair[True]:.2f}x)", file=sys.stderr)
    return winners


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, interpret-safe (CPU CI)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--emit-config", action="store_true",
                    help="print a tuning_configs 'prefill' section with "
                         "each shape's winner on stdout")
    ap.add_argument("--sweep-ingest", action="store_true",
                    help="also A/B the fused prefill ingest "
                         "(prefill.fused_ingest) per shape")
    args = ap.parse_args()
    winners = sweep(args.smoke, args.repeats)
    ingest_winners = (sweep_ingest(args.smoke, args.repeats)
                      if args.sweep_ingest else {})
    if args.emit_config:
        out = {"prefill": {
            "comment": "measured by benchmarks/bench_prefill_blocks.py "
                       "(replace the shipped seed section with this)",
            "seed": bool(args.smoke),
            "tactics": winners,
        }}
        if ingest_winners:
            out["prefill_ingest"] = {
                "comment": "measured by benchmarks/bench_prefill_blocks"
                           ".py --sweep-ingest (replace the shipped "
                           "seed section with this)",
                "seed": bool(args.smoke),
                "tactics": ingest_winners,
            }
        print(json.dumps(out, indent=1))
    else:
        print(json.dumps({"winners": winners,
                          **({"ingest_winners": ingest_winners}
                             if ingest_winners else {})}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
