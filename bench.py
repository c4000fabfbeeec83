"""Headline benchmark: batched paged-KV decode attention on one TPU chip.

Wedge-proof orchestration (the round-2 lesson: a wedged chip must yield a
parseable JSON line with partial results, never rc=124):

* Default invocation is an **orchestrator** that never touches the TPU
  itself.  It (1) probes chip health in a subprocess under a timeout,
  (2) runs each bench *phase* in its own subprocess with its own timeout,
  (3) parses ``ROW {json}`` lines incrementally so a mid-phase hang still
  salvages every measurement that landed, and (4) always prints ONE JSON
  line — with ``"wedged": true`` and whatever partial results exist if
  anything hung.
* Every first compile inside a phase goes through
  ``compile_guard.guarded`` (quarantine protocol), closing the unguarded
  ad-hoc-bench hole that wedged round 2.

Ports the reference's ``benchmarks/bench_batch_decode.py`` headline config
(Llama-3 GQA 32/8 heads, head_dim 128, page 16; see BASELINE.md metric #2):

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric: achieved HBM bandwidth (TB/s) of ``BatchDecodeWithPagedKVCacheWrapper``
at bs=64, ctx=4096 — decode attention is bandwidth-bound, so TB/s is the
hardware-honest throughput number (testing/utils.py attention_tb_per_sec
equivalent).  ``vs_baseline`` = fraction of this chip's HBM peak (v5e ~0.82
TB/s), i.e. roofline efficiency — the reference publishes no absolute
numbers (BASELINE.md), so roofline fraction is the comparable.

Roofline attribution: every row is stamped by the shared cost model
(``obs.costmodel`` formulas x ``obs.hwspec`` chip ceilings via
``obs.roofline.stamp_row``), so each carries ``{flops, bytes_read,
bytes_written, intensity, bound, pct_roofline,
effective_pct_roofline, chip, dtype}`` uniformly — no phase computes
FLOP/byte/peak arithmetic inline, and ``python -m flashinfer_tpu.obs
perf`` reproduces every efficiency fraction from the banked rows.

``--bank`` appends the full run record (configs + timestamps + rows) to
``BENCH_BANKED.md`` so numbers survive a later wedge.
"""

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

PROBE_TIMEOUT_S = 330.0
PHASE_TIMEOUT_S = {
    # generous: each cell may include a fresh Mosaic compile; sweep decode
    # has 16 cells
    "sampling": 1200.0,
    "decode": 1500.0,
    "decode_sweep": 3600.0,
    # 4 split candidates x (compile + measure) on the cliff cell + the
    # long-context control
    "decode_splits": 1800.0,
    "decode_splits_sweep": 2400.0,
    "moe": 1500.0,
    "moe_sweep": 2400.0,
    "topk": 1200.0,
    "scans": 1500.0,
    # serving includes the phase-decomposition micro-loops (6 extra
    # guarded first compiles) on top of the slope +
    # e2e measurements
    "serving": 3000.0,
    # fused + per-op + slope: three guarded first compiles of the same
    # step pipeline
    "serving_fused": 1800.0,
    # sharded fused + per-op + slope over the whole mesh: three guarded
    # first GSPMD compiles (collectives included)
    "serving_sharded": 2400.0,
    # 1000+ requests through the engine TWICE (sharing + the no-sharing
    # bitwise oracle), thousands of host-scheduled step dispatches
    "serving_engine": 2400.0,
    # unified vs disagg (two full engine runs) + the spill-capacity
    # leg, all host-scheduled CPU-provable mechanics
    "serving_disagg": 1800.0,
    "prefill": 1500.0,
    "prefill_sweep": 2400.0,
    "mla": 1200.0,
    "mla_sweep": 2400.0,
}


def _stamp(row, cost, seconds, **split_meta):
    """Stamp the canonical roofline fields onto a row via the shared
    model (obs.roofline x obs.hwspec detection) — THE only path from a
    measurement to an efficiency fraction in this file.  ``split_meta``
    forwards the split-KV stamp fields (num_splits / merge_bytes)."""
    from flashinfer_tpu.obs import hwspec, roofline

    return roofline.stamp_row(row, cost, seconds, hwspec.current_spec(),
                              **split_meta)


_AUDITOR = None


def _emit_row(**kw):
    """Phase-side: one measurement, parseable by the orchestrator.

    Every row passes through the obs quality auditor (self-auditing
    bench telemetry, VERDICT weak #3): the row's throughput metric is
    compared against the best banked/run measurement of the SAME
    configuration and stamped ``quality: ok|degraded|poison`` using the
    committed ``<0.35x best`` implausibility rule — poison rows are
    machine-flagged at emit time instead of by manual cross-checking.
    """
    global _AUDITOR
    try:
        if _AUDITOR is None:
            from flashinfer_tpu.obs import bench_audit

            _AUDITOR = bench_audit.RowAuditor(
                bench_audit.load_banked_history(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_BANKED.md")))
        _AUDITOR.stamp(kw)
        from flashinfer_tpu import obs

        obs.counter_inc("bench.rows", phase=str(kw.get("phase")),
                        quality=kw.get("quality", "unknown"))
    except Exception as e:  # noqa: BLE001 - the audit must never cost a row
        print(f"# row audit failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    print("ROW " + json.dumps(kw), flush=True)


# --------------------------------------------------------------------------
# Phases (run in subprocesses; each initializes the TPU backend itself)
# --------------------------------------------------------------------------


def _guard(name, statics, thunk):
    from flashinfer_tpu import compile_guard

    return compile_guard.guarded(name, statics, thunk)


def _guard_soft(name, statics, thunk):
    """`_guard` that records a failure and returns None instead of raising:
    one failing variant must not cost a phase's remaining rows (the first
    hardware run lost the moe int8 A/B and every scans decode row to the
    first Mosaic compile error in the phase)."""
    try:
        return _guard(name, statics, thunk)
    except Exception as e:  # noqa: BLE001 - record + continue
        first = (str(e).splitlines() or ["<no message>"])[0][:140]
        print(f"# {name} FAILED {type(e).__name__}: {first}",
              file=sys.stderr)
        return None


def _probed_overlap(stepfn, x0, layer_ws, caches, head, head_s, p, l, sk,
                    steps=8, warm=2):
    """Host/device overlap of a raw serving-step callable, measured with
    a PER-STEP completion probe (the obs.steploop gate-ON protocol) in a
    window SEPARATE from wall(): the pipelined us_step throughput number
    must not pay the probe tax.  Returns the ``host_gap_us`` /
    ``host_frac`` measurement stamps (ISSUE 17) — gap = dispatch(N+1)
    return minus step N's completion, host_frac = Σgap/(Σgap+Σdevice)
    over the steady-state pairs, same math as ``steploop.summarize``."""
    import jax

    for _ in range(warm):
        tok, caches, p, l, sk = stepfn(x0, layer_ws, caches, head,
                                       head_s, p, l, sk)
    jax.block_until_ready(tok)
    marks = []
    for _ in range(steps):
        tok, caches, p, l, sk = stepfn(x0, layer_ws, caches, head,
                                       head_s, p, l, sk)
        td = time.perf_counter()
        jax.block_until_ready(tok)
        marks.append((td, time.perf_counter()))
    gaps = [max(marks[i][0] - marks[i - 1][1], 0.0)
            for i in range(1, len(marks))]
    devs = [marks[i][1] - marks[i][0] for i in range(1, len(marks))]
    gap_sum, dev_sum = sum(gaps), sum(devs)
    srt = sorted(gaps)
    return {
        "host_gap_us": round(srt[len(srt) // 2] * 1e6, 1),
        "host_frac": round(gap_sum / max(gap_sum + dev_sum, 1e-12), 4),
    }


def _host_loop_stamps(summary):
    """``obs.steploop.summarize()`` -> the serving-row measurement
    stamps.  ``pred_step_ratio`` is the drift join's p50 (predicted /
    measured step wall) when the surface priced its steps (the engine
    does); absent otherwise."""
    if not summary or not summary.get("steps"):
        return {}
    out = {}
    if summary.get("host_frac") is not None:
        out["host_frac"] = round(summary["host_frac"], 4)
    gap = (summary.get("gap_us") or {}).get("p50")
    if gap is not None:
        out["host_gap_us"] = round(gap, 1)
    drift = (summary.get("drift") or {}).get("p50")
    if drift:
        out["pred_step_ratio"] = round(drift, 4)
    return out


def _pred_step_ratio(cost, seconds, dtype="int8", ici=False):
    """predicted / measured step wall for a raw-step serving row — the
    same forward predictor the engine's online drift join uses.  On CPU
    the ratio is structural (the predictor prices the detected chip),
    exactly like the kv_migrate predicted-vs-measured join."""
    from flashinfer_tpu.obs import costmodel, hwspec

    spec = hwspec.current_spec()
    pred = costmodel.predict_step_seconds(
        cost, hbm_tbps=spec.hbm_tbps,
        peak_tflops=spec.peak_tflops(dtype),
        ici_gbps=spec.ici_gbps if ici else 0.0)
    return round(pred / max(seconds, 1e-12), 4)


def phase_decode(sweep: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi
    from flashinfer_tpu.obs import costmodel, hwspec
    from flashinfer_tpu.testing import bench_fn_device

    peak = hwspec.current_spec().hbm_tbps

    def bench_one(batch, ctx, page_size=16, num_qo_heads=32, num_kv_heads=8,
                  head_dim=128, dtype=jnp.bfloat16):
        pages_per_req = ctx // page_size
        num_pages = batch * pages_per_req
        rng = np.random.default_rng(0)
        perm = rng.permutation(num_pages).astype(np.int32)
        indptr = np.arange(batch + 1, dtype=np.int32) * pages_per_req
        last_page = np.full((batch,), page_size, np.int32)

        key = jax.random.PRNGKey(0)
        # HND cache layout (TPU-preferred contiguous page DMA)
        kc = jax.random.normal(
            key, (num_pages, num_kv_heads, page_size, head_dim), dtype
        )
        vc = jax.random.normal(
            jax.random.fold_in(key, 1),
            (num_pages, num_kv_heads, page_size, head_dim), dtype,
        )
        q = jax.random.normal(
            jax.random.fold_in(key, 2), (batch, num_qo_heads, head_dim), dtype
        )

        w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="HND")
        # num_splits=1 pins the PROVEN unsplit kernel: this phase is the
        # official headline metric and its rows compete with the banked
        # unsplit history — the split path (never yet run on chip) is
        # measured by phase_decode_splits, whose rows carry num_splits
        # identity.  Without the pin, the shipped decode.splits seeds
        # would silently reroute the (256,512)/(64,512) cells here.
        w.plan(indptr, perm, last_page, num_qo_heads, num_kv_heads,
               head_dim, page_size, num_splits=1)

        # Slope-fit in-jit loop timing (bench_fn_device docstring): the
        # per-call overhead cancels.  The whole first call —
        # including the Mosaic compile of the loop body — runs guarded.
        t = _guard(
            "bench.decode", (batch, ctx, page_size, num_qo_heads,
                             num_kv_heads, head_dim, str(dtype)),
            lambda: bench_fn_device(
                lambda qq, kk, vv: w.run(qq, (kk, vv)), q, kc, vc, repeats=5
            ),
        )
        cost = costmodel.paged_decode(batch, ctx, num_qo_heads,
                                      num_kv_heads, head_dim)
        return t, cost.bytes_total / t / 1e12, batch / t, cost

    grid = ([(1, 512), (1, 2048), (1, 4096), (1, 8192),
             (16, 512), (16, 2048), (16, 4096), (16, 8192),
             (64, 512), (64, 2048), (64, 4096), (64, 8192),
             (256, 512), (256, 2048), (256, 4096), (256, 8192)]
            if sweep else [(64, 4096)])
    # headline config first: if the phase dies mid-sweep, the deliverable
    # number is already banked
    grid.sort(key=lambda bc: bc != (64, 4096))
    best_tbps = 0.0
    for bs, ctx in grid:
        t, tbps, tps, cost = bench_one(bs, ctx)
        if (bs, ctx) == (64, 4096):
            # headline cell: run-to-run spread was ~4% on the one day
            # this was measured (0.718-0.745 TB/s, three runs); a second
            # independent measurement minutes apart costs ~1 min and the
            # min-time (max-bandwidth) of the two rejects a degraded
            # window poisoning the deliverable number
            t2, tbps2, tps2, _ = bench_one(bs, ctx)
            if t2 < t:
                t, tbps, tps = t2, tbps2, tps2
        elif bs >= 16 and best_tbps > 0 and tbps < 0.35 * best_tbps:
            # implausible row: a degraded window (~100x slowdowns
            # lasting tens of seconds, see testing/utils.py) can outlast
            # even the timer's cross-scale check — the 2026-07-31 sweep
            # banked 0.0378 TB/s for a shape the same process measured at
            # 0.73 minutes earlier.  One re-measure after a pause, keep
            # the faster (bandwidth at bs>=16 varies ~2x across the grid,
            # never ~20x).
            print(f"# decode bs={bs} ctx={ctx}: {tbps:.4f} TB/s "
                  f"implausible vs best {best_tbps:.4f}; re-measuring",
                  file=sys.stderr)
            time.sleep(20)
            t2, tbps2, tps2, _ = bench_one(bs, ctx)
            if t2 < t:
                t, tbps, tps = t2, tbps2, tps2
        best_tbps = max(best_tbps, tbps)
        _emit_row(**_stamp(
            dict(phase="decode", bs=bs, ctx=ctx, us=round(t * 1e6, 1),
                 tbps=round(tbps, 4), tok_s=round(tps, 0), peak=peak),
            cost, t))
        print(f"# decode bs={bs:4d} ctx={ctx:5d}: {t*1e6:9.1f} us  "
              f"{tbps:6.3f} TB/s  {tps:10.0f} tok/s", file=sys.stderr)


def phase_decode_splits(sweep: bool):
    """Split-KV decode A/B on the short-context cliff cell (ISSUE 6:
    the bs=256/ctx=512 rows swing 0.21-0.54 TB/s while long-context
    decode sits at 0.88-0.91 of roofline).  Runs the wrapper end to end
    at every forced split factor plus the plan-time AUTO selection, so
    the banked rows prove (a) what each S measures and (b) that the
    cost-model chooser picked the winner.  Deeper candidate sweeps live
    in benchmarks/bench_decode_splits.py (kernel-level, --emit-config)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi
    from flashinfer_tpu.obs import costmodel, hwspec
    from flashinfer_tpu.ops.paged_decode import split_pages_per_chunk
    from flashinfer_tpu.testing import bench_fn_device

    chip = hwspec.current_spec()

    def bench_one(batch, ctx, num_splits, page_size=16, num_qo_heads=32,
                  num_kv_heads=8, head_dim=128, dtype=jnp.bfloat16):
        pages_per_req = ctx // page_size
        num_pages = batch * pages_per_req
        rng = np.random.default_rng(0)
        perm = rng.permutation(num_pages).astype(np.int32)
        indptr = np.arange(batch + 1, dtype=np.int32) * pages_per_req
        last_page = np.full((batch,), page_size, np.int32)
        key = jax.random.PRNGKey(0)
        kc = jax.random.normal(
            key, (num_pages, num_kv_heads, page_size, head_dim), dtype)
        vc = jax.random.normal(
            jax.random.fold_in(key, 1),
            (num_pages, num_kv_heads, page_size, head_dim), dtype)
        q = jax.random.normal(
            jax.random.fold_in(key, 2),
            (batch, num_qo_heads, head_dim), dtype)
        w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="HND")
        w.plan(indptr, perm, last_page, num_qo_heads, num_kv_heads,
               head_dim, page_size, num_splits=num_splits)
        S = w._plan.num_splits
        t = _guard_soft(
            "bench.decode_splits",
            (batch, ctx, page_size, num_qo_heads, num_kv_heads,
             head_dim, str(dtype), S),
            lambda: bench_fn_device(
                lambda qq, kk, vv: w.run(qq, (kk, vv)), q, kc, vc,
                repeats=5),
        )
        if t is None:
            return None
        ppc = split_pages_per_chunk(page_size, num_kv_heads, head_dim, 2)
        cost = costmodel.decode_split(
            batch, ctx, num_qo_heads, num_kv_heads, head_dim,
            num_splits=S, page_size=page_size, pages_per_chunk=ppc)
        bd = costmodel.decode_split_breakdown(
            batch, ctx, num_qo_heads, num_kv_heads, head_dim,
            num_splits=S, page_size=page_size, pages_per_chunk=ppc)
        tbps = cost.bytes_total / t / 1e12
        return t, tbps, S, cost, bd

    if os.environ.get("BENCH_SMALL"):
        grid, shape = [(4, 128)], dict(
            num_qo_heads=8, num_kv_heads=2, head_dim=64, page_size=16)
    else:
        grid = ([(256, 512), (64, 512), (64, 4096)] if sweep
                else [(256, 512)])
        shape = {}
    for bs, ctx in grid:
        for forced in (1, 2, 4, None):  # None = plan-time auto choice
            r = bench_one(bs, ctx, forced, **shape)
            if r is None:
                continue
            t, tbps, S, cost, bd = r
            _emit_row(**_stamp(
                dict(phase="decode_splits", bs=bs, ctx=ctx,
                     mode="auto" if forced is None else "forced",
                     us=round(t * 1e6, 1), tbps=round(tbps, 4),
                     peak=chip.hbm_tbps),
                cost, t, num_splits=S, merge_bytes=bd["merge_bytes"]))
            mode = "auto" if forced is None else "forced"
            print(f"# decode_splits bs={bs:4d} ctx={ctx:5d} "
                  f"S={S} ({mode}): {t*1e6:9.1f} us  "
                  f"{tbps:6.4f} TB/s", file=sys.stderr)


def phase_prefill(sweep: bool):
    """Batch chunked prefill TFLOPS (BASELINE.md tracked metric #3:
    BatchPrefillWithPagedKVCacheWrapper) + the ragged flash self-attention
    form, Llama-3-8B GQA shapes."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import flashinfer_tpu as fi
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.testing import bench_fn_device

    if os.environ.get("BENCH_SMALL"):
        HQ, HKV, D, PS = 4, 2, 64, 8
        paged_cfgs, ragged_ts = [(2, 64, 128)], (256,)
    else:
        HQ, HKV, D, PS = 32, 8, 128, 16
        paged_cfgs = ([(8, 512, 4096), (2, 2048, 8192), (16, 256, 2048)]
                      if sweep else [(8, 512, 4096)])
        ragged_ts = (4096, 8192) if sweep else (8192,)

    for bs, qlen, ctx in paged_cfgs:
        ppr = ctx // PS
        npages = bs * ppr
        key = jax.random.PRNGKey(0)
        kc = jax.random.normal(key, (npages, HKV, PS, D), jnp.bfloat16)
        vc = jax.random.normal(jax.random.fold_in(key, 1),
                               (npages, HKV, PS, D), jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (bs * qlen, HQ, D), jnp.bfloat16)
        w = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="HND")
        w.plan(
            np.arange(bs + 1, dtype=np.int32) * qlen,
            np.arange(bs + 1, dtype=np.int32) * ppr,
            np.random.default_rng(0).permutation(npages).astype(np.int32),
            np.full((bs,), PS, np.int32),
            HQ, HKV, D, PS, causal=True,
        )
        t = _guard_soft(
            "bench.prefill", (bs, qlen, ctx, HQ, HKV, D, PS),
            lambda: bench_fn_device(
                lambda qq, kk, vv: w.run(qq, (kk, vv)), q, kc, vc,
                repeats=3,
            ),
        )
        if t is None:
            continue
        # block-config metadata: which pipelined-kernel launch shape this
        # number belongs to (None fields = gather+flash fallback ran) —
        # the row is meaningless for tuning without it
        # (benchmarks/bench_prefill_blocks.py sweeps these knobs)
        cfg = w.fused_prefill_config or {}
        # launched work from the live plan's post-pruning/post-packing
        # stats (effective work = attended tokens); banked `tflops`
        # stays the EFFECTIVE number — comparable across block configs
        cost = costmodel.paged_prefill(
            bs, qlen, ctx, HQ, HKV, D, causal=True,
            stats=w.fused_prefill_stats, block_q=cfg.get("block_q"),
            pages_per_chunk=cfg.get("pages_per_chunk"), page_size=PS)
        _emit_row(**_stamp(
            dict(phase="prefill", kind="paged_chunked", bs=bs, qlen=qlen,
                 ctx=ctx, block_q=cfg.get("block_q"),
                 pages_per_chunk=cfg.get("pages_per_chunk"),
                 num_units=cfg.get("num_units"),
                 us=round(t * 1e6, 1),
                 tflops=round(cost.effective_flops / t / 1e12, 2)),
            cost, t))
        print(f"# prefill paged bs={bs} qlen={qlen} ctx={ctx} "
              f"bq={cfg.get('block_q')} ppc={cfg.get('pages_per_chunk')}: "
              f"{t*1e6:9.1f} us  "
              f"{cost.effective_flops/t/1e12:6.2f} TFLOP/s",
              file=sys.stderr)

        # fused-ingest A/B pair (ISSUE 14): the SAME run_ingest entry
        # with the plan static flipped — rows carry the fused_ingest
        # IDENTITY stamp (separate banked histories) and the cost
        # model's predicted avoided-HBM delta as a measurement, so
        # `obs perf` joins predicted-vs-measured per shape
        if cfg:
            k_new = jax.random.normal(jax.random.fold_in(key, 3),
                                      (bs * ctx, HKV, D), jnp.bfloat16)
            v_new = jax.random.normal(jax.random.fold_in(key, 4),
                                      (bs * ctx, HKV, D), jnp.bfloat16)
            kc0 = jnp.zeros_like(kc)
            vc0 = jnp.zeros_like(vc)
            bd = costmodel.prefill_ingest_breakdown(
                bs * qlen, bs * ctx, HQ, HKV, D)
            pair = {}
            for mode in (True, False):
                wi = fi.BatchPrefillWithPagedKVCacheWrapper(
                    kv_layout="HND")
                wi.plan(
                    np.arange(bs + 1, dtype=np.int32) * qlen,
                    np.arange(bs + 1, dtype=np.int32) * ppr,
                    np.random.default_rng(0).permutation(npages)
                    .astype(np.int32),
                    np.full((bs,), PS, np.int32),
                    HQ, HKV, D, PS, causal=True, fused_ingest=mode,
                )
                ti = _guard_soft(
                    "bench.prefill.ingest",
                    (bs, qlen, ctx, HQ, HKV, D, PS, mode),
                    lambda: bench_fn_device(
                        lambda qq, kk, vv, kc_, vc_: wi.run_ingest(
                            qq, kk, vv, (kc_, vc_)),
                        q, k_new, v_new, kc0, vc0, repeats=3,
                    ),
                )
                if ti is None:
                    continue
                icost = (costmodel.prefill_ingest(
                    bs * qlen, bs * ctx, HQ, HKV, D,
                    stats=getattr(wi, "_ingest_stats", None),
                    block_q=cfg.get("block_q"),
                    pages_per_chunk=cfg.get("pages_per_chunk"),
                    page_size=PS) if mode
                    # the separate row's wall covers rope + append +
                    # attention: price the three-pass traffic, not
                    # attention alone
                    else costmodel.prefill_ingest_separate(
                        bs * qlen, bs * ctx, HQ, HKV, D, causal=True))
                _emit_row(**_stamp(
                    dict(phase="prefill", kind="paged_ingest", bs=bs,
                         qlen=qlen, ctx=ctx, us=round(ti * 1e6, 1),
                         tflops=round(
                             icost.effective_flops / ti / 1e12, 2)),
                    icost, ti, fused_ingest=mode,
                    ingest_bytes_avoided=bd["bytes_avoided"]))
                pair[mode] = ti
                print(f"# prefill ingest bs={bs} qlen={qlen} ctx={ctx} "
                      f"{'fused   ' if mode else 'separate'}: "
                      f"{ti*1e6:9.1f} us", file=sys.stderr)
            if True in pair and False in pair:
                print(f"# prefill ingest bs={bs} qlen={qlen} ctx={ctx}: "
                      f"predicted {bd['bytes_avoided']/1e6:.1f} MB "
                      f"avoided ({bd['avoided_fraction']:.0%} of "
                      f"separate-op bytes); measured oracle/fused "
                      f"{pair[False]/pair[True]:.2f}x", file=sys.stderr)

    for T in ragged_ts:
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (T, HQ, D), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (T, HKV, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (T, HKV, D),
                              jnp.bfloat16)
        t = _guard_soft(
            "bench.prefill.ragged", (T, HQ, HKV, D),
            lambda: bench_fn_device(
                lambda qq, kk, vv: fi.single_prefill_with_kv_cache(
                    qq, kk, vv, causal=True),
                q, k, v, repeats=3,
            ),
        )
        if t is None:
            continue
        cost = costmodel.attention(T, T, HQ, HKV, D, causal=True)
        # block-config metadata: the (block_q, block_kv) _tuned_flash
        # resolves for this shape (THE shared key builder — a hand-copied
        # tuple here would silently desync and bank wrong metadata)
        from flashinfer_tpu.autotuner import AutoTuner
        from flashinfer_tpu.prefill import (
            _FLASH_BLOCK_CANDIDATES, flash_block_key,
        )

        fkey = flash_block_key(T, T, HQ, HKV, D, "bfloat16", True)
        fbq, fbkv = AutoTuner.get().lookup(
            "flash_attention.blocks", fkey,
            default=_FLASH_BLOCK_CANDIDATES[0])
        _emit_row(**_stamp(
            dict(phase="prefill", kind="ragged_flash", qlen=T,
                 block_q=int(fbq), block_kv=int(fbkv),
                 us=round(t * 1e6, 1),
                 tflops=round(cost.flops / t / 1e12, 2)),
            cost, t))
        print(f"# prefill ragged T={T}: {t*1e6:9.1f} us  "
              f"{cost.flops/t/1e12:6.2f} TFLOP/s", file=sys.stderr)


def phase_mla(sweep: bool):
    """MLA absorbed decode (BASELINE.md tracked metric #4: DeepSeek-V3
    ckv 512 + kpe 64): bandwidth vs roofline — the latent cache is read
    ONCE for all 128 heads, the MLA memory win."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.obs import costmodel, hwspec
    from flashinfer_tpu.ops.mla_decode import mla_paged_decode_attention
    from flashinfer_tpu.testing import bench_fn_device

    peak = hwspec.current_spec().hbm_tbps
    if os.environ.get("BENCH_SMALL"):
        H, DC, DP, PS = 8, 128, 64, 8
        cfgs = [(2, 256)]
    else:
        H, DC, DP, PS = 128, 512, 64, 16
        cfgs = [(64, 4096), (16, 4096), (64, 8192)] if sweep \
            else [(64, 4096)]
    for bs, ctx in cfgs:
        ppr = ctx // PS
        npages = bs * ppr
        key = jax.random.PRNGKey(0)
        ckv = jax.random.normal(key, (npages, PS, DC), jnp.bfloat16)
        # TPU-native lane-padded kpe layout (first DP columns live)
        kpe = jnp.pad(
            jax.random.normal(jax.random.fold_in(key, 1),
                              (npages, PS, DP), jnp.bfloat16),
            ((0, 0), (0, 0), (0, 128 - DP)),
        )
        qn = jax.random.normal(jax.random.fold_in(key, 2), (bs, H, DC),
                               jnp.bfloat16)
        qp = jax.random.normal(jax.random.fold_in(key, 3), (bs, H, DP),
                               jnp.bfloat16)
        pt = jnp.asarray(
            np.random.default_rng(0).permutation(npages)
            .astype(np.int32).reshape(bs, ppr)
        )
        lens = jnp.full((bs,), ctx, jnp.int32)
        sc = 1.0 / float(np.sqrt(DC + DP))
        # A/B the two scratch layouts (split = hw-validated default;
        # packed = one concatenated score dot) — the banked pair is the
        # evidence behind the mla_decode.layout tuned tactic
        for layout in ("split", "packed"):
            t = _guard_soft(
                "bench.mla", (bs, ctx, H, DC, DP, PS, layout),
                lambda: bench_fn_device(
                    lambda a, b, c, d: mla_paged_decode_attention(
                        a, b, c, d, pt, lens, sm_scale=sc, layout=layout),
                    qn, qp, ckv, kpe, repeats=3,
                ),
            )
            if t is None:
                continue
            # decode-bound: latent + lane-padded rope caches stream once
            # per request (the dominant term; q/out ride along)
            cost = costmodel.mla_decode(bs, ctx, H, latent_dim=DC,
                                        rope_dim=DP)
            tbps = cost.bytes_total / t / 1e12
            _emit_row(**_stamp(
                dict(phase="mla", bs=bs, ctx=ctx, heads=H, layout=layout,
                     us=round(t * 1e6, 1), tbps=round(tbps, 4),
                     peak=peak),
                cost, t))
            print(f"# mla {layout:6s} bs={bs} ctx={ctx}: {t*1e6:9.1f} us  "
                  f"{tbps:6.3f} TB/s", file=sys.stderr)


def phase_sampling(sweep: bool):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.sampling_kernels import threshold_select
    from flashinfer_tpu.sampling import (
        _top_k_top_p_filter_xla, sampling_from_probs,
    )
    from flashinfer_tpu.testing import bench_fn_device

    def bench_one(batch, vocab, backend):
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (batch, vocab), jnp.float32) * 4.0
        probs = jax.nn.softmax(logits, axis=-1)
        k = jnp.full((batch,), 40.0, jnp.float32)
        tp = jnp.full((batch,), 0.95, jnp.float32)
        if backend == "pallas":
            fn = lambda p, kk: sampling_from_probs(
                threshold_select(p, k, tp, mode="top_k_top_p_seq"), kk
            )
        else:
            fn = lambda p, kk: sampling_from_probs(
                _top_k_top_p_filter_xla(p, k.astype(jnp.int32), tp, False), kk
            )
        return _guard(
            "bench.sampling", (batch, vocab, backend),
            lambda: bench_fn_device(fn, probs, jax.random.PRNGKey(1),
                                    repeats=5),
        )

    if os.environ.get("BENCH_SMALL"):  # CPU smoke: interpret-mode kernel
        vocab, sizes = 1024, (8,)       # at 128k vocab takes minutes/row
    else:
        vocab, sizes = 128 * 1024, ((64, 1, 16) if sweep else (64,))
    from flashinfer_tpu.obs import costmodel

    for bs in sizes:
        tk = bench_one(bs, vocab, "pallas") * 1e6
        tx = bench_one(bs, vocab, "xla") * 1e6
        # kernel_us is the row's primary time: the stamp attributes the
        # kernel path (one f32 pass over [bs, vocab] probs)
        _emit_row(**_stamp(
            dict(phase="sampling", bs=bs, vocab=vocab,
                 kernel_us=round(tk, 1), xla_us=round(tx, 1),
                 speedup=round(tx / tk, 2)),
            costmodel.sampling(bs, vocab), tk * 1e-6))
        print(f"# sampling vocab={vocab} bs={bs:3d}: kernel {tk:8.1f} us  "
              f"xla-sort {tx:8.1f} us  ({tx / tk:4.1f}x)", file=sys.stderr)


def phase_moe(sweep: bool):
    """Fused MoE: Pallas gather-GMM pipeline vs ragged_dot (VERDICT r2 #4).

    Mixtral-8x7B shape (E=8, H=4096, I=14336, K=2) — weights fit v5e HBM
    in bf16; int8 variant also measured (native int8 MXU path)."""
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import fused_moe as moe_pkg
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.testing import bench_fn_device

    if os.environ.get("BENCH_SMALL"):  # CPU smoke of the phase plumbing
        E, H, I, K = 4, 256, 512, 2
        token_counts = {False: (64,), True: (32, 64)}
    else:
        E, H, I, K = 8, 4096, 14336, 2
        token_counts = {False: (1024,), True: (256, 1024)}
    key = jax.random.PRNGKey(0)
    w1 = jax.random.normal(key, (E, H, 2 * I), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(jax.random.fold_in(key, 1), (E, I, H),
                           jnp.bfloat16) * 0.02
    w1q, w1s = quantize_int8(w1, axis=1)
    w2q, w2s = quantize_int8(w2, axis=1)

    for T in token_counts[sweep]:
        x = jax.random.normal(jax.random.fold_in(key, 2), (T, H),
                              jnp.bfloat16)
        logits = jax.random.normal(jax.random.fold_in(key, 3), (T, E),
                                   jnp.float32)
        wts, ids = moe_pkg.route_renormalize(logits, K)
        # weights ride as operands — bench_fn_device forbids closing over
        # large arrays (they'd embed as HLO constants)
        def bf16_fn(backend, gv="auto"):
            return lambda xx, ww, ii, a, b: moe_pkg.fused_moe(
                xx, a, b, ww, ii, E, backend=backend, gather_variant=gv)

        def int8_fn(backend, gv="auto"):
            return lambda xx, ww, ii, a, b, sa, sb: moe_pkg.fused_moe(
                xx, a, b, ww, ii, E, w1_scale=sa, w2_scale=sb,
                backend=backend, gather_variant=gv)

        # A/B: ragged_dot vs the tuned-tile sorted-gather GMM (the auto
        # default on hardware since the 2026-07-31 tile sweep,
        # BENCH_BANKED.md).  The stream/rowcache gather variants are NOT
        # benched: Mosaic rejects their in-kernel per-row gather ("Slice
        # shape along dimension 0 must be aligned to tiling (8)") on this
        # chip generation — permanently xfail-documented in the hw tier,
        # so a guarded compile failure per sweep bought nothing.
        # Per-variant isolation: one failing variant must not cost the
        # phase's remaining rows.
        for name, fn, ops in (
            ("ragged_bf16", bf16_fn("ragged"), (w1, w2)),
            ("gmm_sorted_bf16", bf16_fn("gmm", "sorted"), (w1, w2)),
            ("ragged_int8", int8_fn("ragged"), (w1q, w2q, w1s, w2s)),
            ("gmm_sorted_int8", int8_fn("gmm", "sorted"),
             (w1q, w2q, w1s, w2s)),
        ):
            t = _guard_soft(
                f"bench.moe.{name}", (T, E, H, I, K),
                lambda: bench_fn_device(fn, x, wts, ids, *ops, repeats=3),
            )
            if t is None:
                continue
            int8 = name.endswith("int8")
            cost = costmodel.moe_gmm(T, E, H, I, K,
                                     weight_bytes=1 if int8 else 2,
                                     dtype="int8" if int8 else "bf16")
            _emit_row(**_stamp(
                dict(phase="moe", variant=name, tokens=T,
                     us=round(t * 1e6, 1),
                     tflops=round(cost.flops / t / 1e12, 2)),
                cost, t))
            print(f"# moe {name:12s} T={T:5d}: {t*1e6:9.1f} us  "
                  f"{cost.flops/t/1e12:6.2f} TFLOP/s", file=sys.stderr)


def phase_scans(sweep: bool):
    """Linear-attention/SSM family: chunked prefill + decode step latency
    (VERDICT r2 #6) — pure-XLA paths measured against roofline before any
    Pallas kernel is justified.  Mamba-2 SSD at 2.7B-ish shapes; GDN/KDA
    at 16 heads x 128x128 state."""
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import gdn as gdn_mod
    from flashinfer_tpu import mamba as mamba_mod
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.testing import bench_fn_device

    if os.environ.get("BENCH_SMALL"):
        B, L, H, dim, ds, G = 1, 256, 2, 16, 16, 1
        Hg, dk, dv = 2, 32, 32
    else:
        B, L, H, dim, ds, G = 4, 4096, 24, 64, 128, 1
        Hg, dk, dv = 16, 128, 128
    key = jax.random.PRNGKey(0)

    # --- mamba chunked SSD prefill ---
    x = jax.random.normal(key, (B, L, H, dim), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, L, H)))
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, L, G, ds))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, L, G, ds))
    from flashinfer_tpu.ops import mamba_kernel as _mk

    mamba_variants = [("mamba_prefill", "xla", 64)]
    if _mk.eligible(x, Bm):
        mamba_variants.append(
            ("mamba_prefill_pallas", "pallas", _mk._CHUNK)
        )
    for mname, mbackend, mchunk in mamba_variants:
        t = _guard_soft(
            f"bench.scans.{mname}", (B, L, H, dim, ds),
            lambda: bench_fn_device(
                lambda *a: mamba_mod.mamba_chunk_scan_combined(
                    *a, backend=mbackend)[0],
                x, dt, A, Bm, Cm, repeats=3,
            ),
        )
        if t is None:
            continue
        # SSD cost: scores [Q,Q] via C.B (ds) + out [Q,dim] per chunk
        # (per-variant chunk: the pallas kernel runs 128-token chunks)
        cost = costmodel.ssd_prefill(B, L, H, dim, ds, chunk=mchunk)
        _emit_row(**_stamp(
            dict(phase="scans", op=mname, B=B, L=L,
                 us=round(t * 1e6, 1),
                 tflops=round(cost.flops / t / 1e12, 2)),
            cost, t))
        print(f"# scans {mname}: {t*1e6:9.1f} us", file=sys.stderr)

    # --- mamba decode step (bandwidth-bound: state RMW) ---
    st = jax.random.normal(key, (B, H, dim, ds), jnp.float32)
    xd = jax.random.normal(jax.random.fold_in(key, 5), (B, H, dim))
    dtd = jax.random.normal(jax.random.fold_in(key, 6), (B, H, dim))
    Ad = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 7),
                                    (H, dim, ds)))
    Bd = jax.random.normal(jax.random.fold_in(key, 8), (B, G, ds))
    Cd = jax.random.normal(jax.random.fold_in(key, 9), (B, G, ds))
    # decode steps are state-bandwidth-bound (the [.., dk, dv] f32 state
    # is read+written once per token); pct_roofline (stamped by the
    # shared model, 0..1 fraction) is the go/no-go signal for a Pallas
    # decode kernel (VERDICT r3 #8): XLA already streaming near roofline
    # = no kernel justified
    # bench the WHOLE (y, new_state) tuple — selecting [1] would let XLA
    # dead-code-eliminate the output projection (y depends on the state,
    # never vice versa) and under-report every decode step
    t = _guard_soft(
        "bench.scans.mamba_decode", (B, H, dim, ds),
        lambda: bench_fn_device(
            mamba_mod.selective_state_update,
            st, xd, dtd, Ad, Bd, Cd, repeats=5,
        ),
    )
    if t is not None:
        cost = costmodel.state_decode(B, H, dim, ds)
        _emit_row(**_stamp(
            dict(phase="scans", op="mamba_decode", B=B,
                 us=round(t * 1e6, 1),
                 gbps=round(cost.bytes_total / t / 1e9, 1)),
            cost, t))
        print(f"# scans mamba_decode:  {t*1e6:9.1f} us", file=sys.stderr)

    # --- GDN / KDA decode steps (same roofline protocol) ---
    sg = jax.random.normal(key, (B, Hg, dk, dv), jnp.float32)
    qd = jax.random.normal(jax.random.fold_in(key, 20), (B, Hg, dk)) * 0.3
    kd = jax.random.normal(jax.random.fold_in(key, 21), (B, Hg, dk)) * 0.3
    vd = jax.random.normal(jax.random.fold_in(key, 22), (B, Hg, dv))
    bd = jax.nn.sigmoid(
        jax.random.normal(jax.random.fold_in(key, 23), (B, Hg)))
    ag_d = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 24), (B, Hg)))
    ak_d = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 25), (B, Hg, dk)))
    for dname, dfn, da in (
        ("gdn_decode", gdn_mod.gdn_decode_step, ag_d),
        ("kda_decode", gdn_mod.kda_decode_step, ak_d),
    ):
        t = _guard_soft(
            f"bench.scans.{dname}", (B, Hg, dk, dv),
            lambda: bench_fn_device(dfn, sg, qd, kd, vd, da, bd,
                                    repeats=5),
        )
        if t is None:
            continue
        cost = costmodel.state_decode(B, Hg, dk, dv)
        _emit_row(**_stamp(
            dict(phase="scans", op=dname, B=B, us=round(t * 1e6, 1),
                 gbps=round(cost.bytes_total / t / 1e9, 1)),
            cost, t))
        print(f"# scans {dname}:  {t*1e6:9.1f} us", file=sys.stderr)

    # --- GDN / KDA chunked prefill ---
    q = jax.random.normal(key, (B, L, Hg, dk), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 10),
                          (B, L, Hg, dk)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 11), (B, L, Hg, dv))
    beta = jax.nn.sigmoid(
        jax.random.normal(jax.random.fold_in(key, 12), (B, L, Hg))
    )
    alpha_g = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 13), (B, L, Hg)))
    alpha_k = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 14), (B, L, Hg, dk)))
    # explicit backend="xla": auto now resolves to the pallas kernel on
    # these eligible shapes (flipped on this A/B's own rows), so the
    # baseline must pin XLA or the A/B measures the kernel against itself
    variants = [
        ("gdn_prefill",
         lambda *a: gdn_mod.gdn_chunk_prefill(*a, backend="xla")[0],
         alpha_g),
        ("kda_prefill",
         lambda *a: gdn_mod.kda_chunk_prefill(*a, backend="xla")[0],
         alpha_k),
    ]
    from flashinfer_tpu.ops import gdn_kernel as _gk

    if _gk.eligible(q, v):
        # fused VMEM-resident kernels (ops/gdn_kernel.py): the backend
        # A/Bs the banked sweep decides on (BENCH_SMALL dims are too
        # small for their 128-aligned tiles)
        variants.insert(1, (
            "gdn_prefill_pallas",
            lambda *a: gdn_mod.gdn_chunk_prefill(*a, backend="pallas")[0],
            alpha_g,
        ))
        variants.append((
            "kda_prefill_pallas",
            lambda *a: gdn_mod.kda_chunk_prefill(*a, backend="pallas")[0],
            alpha_k,
        ))
    for name, fn, aa in variants:
        t = _guard_soft(
            f"bench.scans.{name}", (B, L, Hg, dk, dv),
            lambda: bench_fn_device(fn, q, k, v, aa, beta, repeats=3),
        )
        if t is None:
            continue
        cost = costmodel.gated_delta_prefill(B, L, Hg, dk, dv)
        _emit_row(**_stamp(
            dict(phase="scans", op=name, B=B, L=L,
                 us=round(t * 1e6, 1),
                 tflops=round(cost.flops / t / 1e12, 2)),
            cost, t))
        print(f"# scans {name}: {t*1e6:9.1f} us", file=sys.stderr)


def phase_topk(sweep: bool):
    """Exact top-k at 128k vocab: threshold-bisection kernel vs XLA sort
    (VERDICT r2 #7) — the sparse-MLA selection feeder."""
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import topk as topk_mod
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.testing import bench_fn_device

    if os.environ.get("BENCH_SMALL"):
        bs, vocab, ks = 8, 2048, (16,)
    else:
        bs, vocab, ks = 64, 128 * 1024, (40, 2048)
    key = jax.random.PRNGKey(0)
    scores = jax.random.normal(key, (bs, vocab), jnp.float32) * 4.0

    for k in ks:
        for backend in ("xla", "threshold"):
            fn = lambda s: topk_mod.top_k_values_indices(s, k, backend)[1]
            t = _guard(
                f"bench.topk.{backend}", (bs, vocab, k),
                lambda: bench_fn_device(fn, scores, repeats=5),
            )
            _emit_row(**_stamp(
                dict(phase="topk", backend=backend, bs=bs, vocab=vocab,
                     k=k, us=round(t * 1e6, 1)),
                costmodel.topk(bs, vocab, k), t))
            print(f"# topk {backend:10s} k={k:5d}: {t*1e6:9.1f} us",
                  file=sys.stderr)


def phase_serving(sweep: bool):
    """North-star serving number (BASELINE.md): Llama-3-70B batch decode,
    bs=64, ctx=4k, tokens/sec/chip.

    One v5e chip holds the tp=8 PER-CHIP SHARD of the 70B (8 q heads /
    1 kv head / inter 3584 per chip), int8 weights + int8 KV (the v5e
    low-precision serving story; a bf16 70B shard does not fit 16 GB).
    The decode step is the real op pipeline — rmsnorm -> fused-int8 qkv
    -> RoPE -> fused int8-KV paged decode attention -> o/mlp int8 GEMMs
    -> lm_head shard — measured at TWO layer depths; the per-layer slope
    extrapolates to 80 layers (the two-point fit also validates
    linearity, printed as a sanity row).  EXCLUDED from the SLOPE row
    only: the 2 per-layer ICI all-reduces (one chip) and per-step KV
    appends (~64 tokens x 256 B, noise vs the 14 GB/step HBM sweep).  The kv-append exclusion is historical to
    this row, not to the serving story: the e2e cross-check below and
    the ``serving_fused`` phase's compile-once fused step
    (flashinfer_tpu.serve) both INCLUDE the per-layer quantize+scatter
    append — the fused step never excludes it.

    Scale conventions (sm_scale*k_scale folding, output *v_scale) follow
    the models/llama.py int8-KV contract and tests/test_quant_kv.py; the
    pipeline is inlined rather than driving models/llama.py because the
    model runs full-width bf16 layers with mesh collectives — the
    per-chip int8-weight shard benched here is a different program.  If
    models/llama.py ever grows an int8-weight mode, fold this into it.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.ops import paged_decode_attention
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.rope import apply_rope_pos_ids
    from flashinfer_tpu.testing import bench_fn_device

    if os.environ.get("BENCH_SMALL"):
        bs, ctx, PS = 4, 128, 16
        hidden, hq, hkv, hd, inter, vocab_shard = 512, 4, 1, 128, 1024, 1024
        depths, full_layers = (2, 4), 8
    else:
        bs, ctx, PS = 64, 4096, 16
        hidden, hq, hkv, hd, inter, vocab_shard = 8192, 8, 1, 128, 3584, 16032
        depths, full_layers = (8, 16), 80
    pages_per_req = ctx // PS
    num_pages = bs * pages_per_req
    qdim, kvdim = hq * hd, hkv * hd
    key = jax.random.PRNGKey(0)

    def qw(k, shape, axis=0):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=axis)
        return wq, ws.reshape(1, -1)

    def build(L):
        ks = jax.random.split(jax.random.fold_in(key, L), 6 * L + 2)
        stack = lambda parts: tuple(jnp.stack(p) for p in zip(*parts))
        layers = stack([
            (
                *qw(ks[6 * i], (hidden, qdim + 2 * kvdim)),
                *qw(ks[6 * i + 1], (qdim, hidden)),
                *qw(ks[6 * i + 2], (hidden, 2 * inter)),
                *qw(ks[6 * i + 3], (inter, hidden)),
                jax.random.normal(ks[6 * i + 4], (hidden,)) * 0.02 + 1.0,
                jax.random.normal(ks[6 * i + 5], (hidden,)) * 0.02 + 1.0,
            )
            for i in range(L)
        ])
        kc = jax.random.randint(
            ks[-2], (L, num_pages, hkv, PS, hd), -127, 127, jnp.int8
        )
        vc = jax.random.randint(
            ks[-1], (L, num_pages, hkv, PS, hd), -127, 127, jnp.int8
        )
        head, head_s = qw(jax.random.fold_in(key, 999), (hidden, vocab_shard))
        return layers, kc, vc, head, head_s

    pt = jnp.asarray(
        np.random.default_rng(0).permutation(num_pages)
        .reshape(bs, pages_per_req).astype(np.int32)
    )
    lens = jnp.full((bs,), ctx - 1, jnp.int32)
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (bs, hidden),
                           jnp.bfloat16)
    kscale = vscale = 0.05
    sm = hd ** -0.5

    inv_k, inv_v = 1.0 / kscale, 1.0 / vscale

    from flashinfer_tpu.profiler import scope as _scope

    def _layer(x, w, kcl, vcl, lens, pt, append):
        """One decoder layer on the int8 shard pipeline; ``append=True``
        additionally quantizes + scatters the new token's K/V into the
        paged cache before attention (the real serving write path).
        The named scopes label device traces with the SAME phase names
        the overhead_decomposition row uses (obs catalog
        serving.phase_us), so a jax.profiler capture cross-checks the
        micro-loop numbers.  TWIN: serve/shard.py shard_layer is the
        library copy of this math (the serving_fused phase's
        substrate); the banked rows here were hardware-measured under
        THIS inline code, so edits must be mirrored — see the TWIN
        NOTE in serve/shard.py."""
        wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2 = w
        with _scope("serving.norm_rope"):
            h = rmsnorm(x, n1.astype(x.dtype))
        with _scope("serving.attention"):
            hq8, hs = quantize_int8(h)
            qkv = mm_int8(hq8, wqkv, hs, sqkv)
            q = qkv[:, :qdim].reshape(bs, hq, hd)
            k = qkv[:, qdim:qdim + kvdim].reshape(bs, hkv, hd)
        with _scope("serving.norm_rope"):
            q, k = apply_rope_pos_ids(q, k, lens)
        attn_lens = lens
        if append:
            with _scope("serving.kv_append"):
                v = qkv[:, qdim + kvdim:].reshape(bs, hkv, hd)
                pages = jnp.take_along_axis(
                    pt, lens[:, None] // PS, axis=1)[:, 0]
                slots = lens % PS
                k8 = jnp.clip(jnp.round(k * inv_k), -127, 127) \
                    .astype(jnp.int8)
                v8 = jnp.clip(jnp.round(v * inv_v), -127, 127) \
                    .astype(jnp.int8)
                kcl = kcl.at[pages, :, slots, :].set(k8)
                vcl = vcl.at[pages, :, slots, :].set(v8)
            attn_lens = lens + 1
        with _scope("serving.attention"):
            attn = paged_decode_attention(
                q.astype(jnp.bfloat16), kcl, vcl, pt, attn_lens,
                sm_scale=sm * kscale, kv_layout="HND",
            ) * vscale
            a8, as_ = quantize_int8(attn.reshape(bs, qdim))
            x = x + mm_int8(a8, wo, as_, so)
        with _scope("serving.norm_rope"):
            h2 = rmsnorm(x, n2.astype(x.dtype))
        with _scope("serving.moe_or_mlp"):
            g8, gs = quantize_int8(h2)
            mlp = silu_and_mul(mm_int8(g8, wgu, gs, sgu))
            m8, ms = quantize_int8(mlp)
            out = (x + mm_int8(m8, wd, ms, sd)).astype(x.dtype)
        return out, kcl, vcl

    def step(x, layers, kc, vc, head, head_s, pt, lens):
        # scan over layers: weights + per-layer caches ride the xs axis
        def body(carry, w):
            *weights, kcl, vcl = w
            x, _, _ = _layer(carry, tuple(weights), kcl, vcl, lens, pt,
                             append=False)
            return x, None

        x, _ = jax.lax.scan(body, x, (*layers, kc, vc))
        hq8, hs = quantize_int8(rmsnorm(x, jnp.ones((hidden,), x.dtype)))
        return mm_int8(hq8, head, hs, head_s, out_dtype=jnp.float32)

    times = {}
    for L in depths:
        layers, kc, vc, head, head_s = build(L)
        t = _guard(
            "bench.serving70b", (bs, ctx, L, hidden),
            lambda: bench_fn_device(
                step, x0, layers, kc, vc, head, head_s, pt, lens, repeats=3
            ),
        )
        times[L] = t
        print(f"# serving L={L}: {t*1e6:9.1f} us/step", file=sys.stderr)
    l1, l2 = depths
    per_layer = (times[l2] - times[l1]) / (l2 - l1)
    fixed = max(times[l1] - l1 * per_layer, 0.0)
    t_full = fixed + full_layers * per_layer
    toks = bs / t_full
    # per-phase cost shapes of THIS run's pipeline (BENCH_SMALL shrinks
    # them, so the model must come from the locals, not SERVING_SHAPES)
    from flashinfer_tpu.obs import costmodel

    serve_shape = dict(hidden=hidden, hq=hq, hkv=hkv, hd=hd, inter=inter,
                       vocab_shard=vocab_shard, page_size=PS,
                       weight_bytes=1, kv_bytes=1)
    # VERDICT r3 weak #6: the 80-layer number is a slope-fit projection from
    # two measured depths on one chip — carry that in the JSON itself so a
    # reader of BENCH_r{N}.json cannot quote it as a measured number.
    _emit_row(**_stamp(
        dict(phase="serving", model="llama70b_tp8shard_int8", bs=bs,
             ctx=ctx, layers_measured=list(depths),
             us_per_layer=round(per_layer * 1e6, 1),
             us_step_80l=round(t_full * 1e6, 1),
             tok_s_per_chip=round(toks, 1),
             linearity=round(times[l2] / times[l1], 3),
             extrapolated=True,
             excluded=["ici_allreduce", "kv_append", "sampling"]),
        costmodel.serving_step(bs, ctx, full_layers,
                               include_kv_append=False,
                               include_sampling=False, **serve_shape),
        t_full))
    print(f"# serving 70B extrapolated: {t_full*1e3:.2f} ms/step, "
          f"{toks:.0f} tok/s/chip", file=sys.stderr)

    # ---- cross-check: a REAL measured end-to-end serve loop at the
    # shallow depth — the SAME ``_layer`` pipeline with ``append=True``
    # (per-layer int8 KV quantize+scatter) plus the final top-k sampling
    # the slope row excludes.  Nothing here is extrapolated; the delta vs
    # the slope model's same-depth prediction bounds what the exclusions
    # cost.  Structure matters for honesty: the caches are threaded
    # through a ``lax.scan`` CARRY over steps (``bench_steps_device``),
    # so XLA's while-body aliasing updates them in place exactly like a
    # donation-based serving loop — re-feeding identical cache inputs per
    # iteration (``bench_fn_device``) would degrade every append into a
    # full-cache copy and measure that artifact instead.  Layers unroll
    # as a Python loop over per-layer cache arrays, mirroring
    # models/llama.py's structure.  ``lens`` stays fixed (each step
    # overwrites the same slot) so every step is shape- and work-
    # identical; the sampled token feeds the next step's PRNG key, which
    # chains the steps without an embed matrix (this shard pipeline has
    # none — x0 re-enters per step).
    from flashinfer_tpu.sampling import sampling_from_logits, top_k_mask_logits
    from flashinfer_tpu.testing import bench_steps_device

    L = l1
    layers, kc, vc, head, head_s = build(L)
    layer_ws = [tuple(a[l] for a in layers) for l in range(L)]
    caches0 = [(kc[l], vc[l]) for l in range(L)]

    def make_serve_loop(n):
        @jax.jit
        def loop(x0, layer_ws, caches, head, head_s, pt, lens, skey):
            def step_body(carry, _):
                caches, skey = carry
                x = x0
                new_caches = []
                for w, (kcl, vcl) in zip(layer_ws, caches):
                    x, kcl, vcl = _layer(x, w, kcl, vcl, lens, pt,
                                         append=True)
                    new_caches.append((kcl, vcl))
                hq8, hs = quantize_int8(
                    rmsnorm(x, jnp.ones((hidden,), x.dtype)))
                logits = mm_int8(hq8, head, hs, head_s,
                                 out_dtype=jnp.float32)
                tok = sampling_from_logits(
                    top_k_mask_logits(logits, 40), skey)
                skey = jax.random.fold_in(skey, tok[0])
                return (new_caches, skey), tok[0]
            (_, _), toks = jax.lax.scan(
                step_body, (caches, skey), None, length=n)
            return toks.sum()
        return loop

    t_e2e = _guard(
        "bench.serving70b_e2e", (bs, ctx, L, hidden),
        lambda: bench_steps_device(
            make_serve_loop, x0, layer_ws, caches0, head, head_s, pt, lens,
            jax.random.PRNGKey(3), repeats=3,
        ),
    )
    pred = fixed + L * per_layer

    # ---- serving-loop phase decomposition (VERDICT weak #2 + #4): the
    # 13-31% overhead_vs_slope tax, attributed by inclusion until now,
    # measured phase by phase.  Each named phase of the decode step runs
    # as its own jitted micro-loop at the EXACT serving shapes (the same
    # slope-fit protocol as every bench row); kv_append threads the
    # caches through a scan carry (bench_steps_device) so the measured
    # write is the aliased in-place one, not a full-cache-copy artifact.
    # residual_us = t_e2e - sum(phases): the per-step cost the phases
    # don't explain — dispatch/scheduling/layer-glue, the number the
    # decode-step NO-GO (weak #4) leaned on without measuring.
    from flashinfer_tpu import obs

    kc0, vc0 = caches0[0]
    wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2 = layer_ws[0]
    dkey = jax.random.fold_in(key, 777)
    qkv_like = jax.random.normal(dkey, (bs, qdim + 2 * kvdim), jnp.bfloat16)
    logits_like = jax.random.normal(jax.random.fold_in(dkey, 1),
                                    (bs, vocab_shard), jnp.float32) * 4.0

    def f_norm_rope(x, n1_, n2_, qkv_, lens_):
        h1 = rmsnorm(x, n1_.astype(x.dtype))
        h2 = rmsnorm(x, n2_.astype(x.dtype))
        q = qkv_[:, :qdim].reshape(bs, hq, hd)
        k = qkv_[:, qdim:qdim + kvdim].reshape(bs, hkv, hd)
        q, k = apply_rope_pos_ids(q, k, lens_)
        return h1 + h2, q, k

    def f_attention(x, wqkv_, sqkv_, wo_, so_, kcl, vcl, pt_, lens_):
        # the attention block incl. its qkv/o int8 projections (rmsnorm
        # and rope live in norm_rope; quantize rides the gemm using it)
        h8, hs = quantize_int8(x)
        qkv = mm_int8(h8, wqkv_, hs, sqkv_)
        q = qkv[:, :qdim].reshape(bs, hq, hd)
        attn = paged_decode_attention(
            q.astype(jnp.bfloat16), kcl, vcl, pt_, lens_,
            sm_scale=sm * kscale, kv_layout="HND",
        ) * vscale
        a8, as_ = quantize_int8(attn.reshape(bs, qdim))
        return mm_int8(a8, wo_, as_, so_)

    def f_mlp(x, wgu_, sgu_, wd_, sd_):
        g8, gs = quantize_int8(x)
        mlp = silu_and_mul(mm_int8(g8, wgu_, gs, sgu_))
        m8, ms = quantize_int8(mlp)
        return mm_int8(m8, wd_, ms, sd_)

    def f_lm_head(x, head_, head_s_):
        h8, hs = quantize_int8(rmsnorm(x, jnp.ones((hidden,), x.dtype)))
        return mm_int8(h8, head_, hs, head_s_, out_dtype=jnp.float32)

    def f_sampling(logits, skey):
        return sampling_from_logits(top_k_mask_logits(logits, 40), skey)

    def make_append_loop(n):
        @jax.jit
        def loop(qkv_, kcl, vcl, pt_, lens_):
            def body(carry, _):
                kcl_, vcl_ = carry
                k = qkv_[:, qdim:qdim + kvdim].reshape(bs, hkv, hd)
                v = qkv_[:, qdim + kvdim:].reshape(bs, hkv, hd)
                pages = jnp.take_along_axis(
                    pt_, lens_[:, None] // PS, axis=1)[:, 0]
                slots = lens_ % PS
                k8 = jnp.clip(jnp.round(k.astype(jnp.float32) * inv_k),
                              -127, 127).astype(jnp.int8)
                v8 = jnp.clip(jnp.round(v.astype(jnp.float32) * inv_v),
                              -127, 127).astype(jnp.int8)
                kcl_ = kcl_.at[pages, :, slots, :].set(k8)
                vcl_ = vcl_.at[pages, :, slots, :].set(v8)
                return (kcl_, vcl_), jnp.float32(0.0)

            (kcl, vcl), _ = jax.lax.scan(body, (kcl, vcl), None, length=n)
            return (jnp.sum(kcl.astype(jnp.float32))
                    + jnp.sum(vcl.astype(jnp.float32))) * 1e-30
        return loop

    phase_benches = (
        ("norm_rope", L, lambda: bench_fn_device(
            f_norm_rope, x0, n1, n2, qkv_like, lens, repeats=2)),
        ("attention", L, lambda: bench_fn_device(
            f_attention, x0, wqkv, sqkv, wo, so, kc0, vc0, pt, lens,
            repeats=2)),
        ("kv_append", L, lambda: bench_steps_device(
            make_append_loop, qkv_like, kc0, vc0, pt, lens, repeats=2)),
        ("moe_or_mlp", L, lambda: bench_fn_device(
            f_mlp, x0, wgu, sgu, wd, sd, repeats=2)),
        ("lm_head", 1, lambda: bench_fn_device(
            f_lm_head, x0, head, head_s, repeats=2)),
        ("sampling", 1, lambda: bench_fn_device(
            f_sampling, logits_like, jax.random.PRNGKey(5), repeats=2)),
    )
    decomp = {}
    for pname, mult, thunk in phase_benches:
        t = _guard_soft(f"bench.serving.decomp_{pname}",
                        (bs, ctx, L, hidden, pname), thunk)
        decomp[pname + "_us"] = (None if t is None
                                 else round(mult * t * 1e6, 2))
        if t is not None:
            obs.observe("serving.phase_us", mult * t * 1e6, phase=pname)
            print(f"# serving decomp {pname}: {mult * t * 1e6:9.1f} us/step",
                  file=sys.stderr)
        else:
            print(f"# serving decomp {pname}: FAILED", file=sys.stderr)
    parts = [v for v in decomp.values() if v is not None]
    decomp["residual_us"] = (
        round(t_e2e * 1e6 - sum(parts), 2)
        if len(parts) == len(phase_benches) else None)
    if decomp["residual_us"] is not None:
        obs.observe("serving.phase_us", max(decomp["residual_us"], 0.0),
                    phase="residual")

    # lifecycle stamps (ISSUE 10): in steady-state batch decode the
    # per-step wall time IS each request's time-per-output-token, so
    # the e2e row carries it under the serving-SLO name (a measurement
    # field, bench_audit.MEASUREMENT_FIELDS).  TTFT needs a prefill ->
    # first-token boundary this decode-only loop does not have; the
    # serving_fused phase measures its first-step analog.
    obs.observe("lifecycle.tpot_us", t_e2e * 1e6)
    _emit_row(**_stamp(
        dict(phase="serving", model="llama70b_tp8shard_int8",
             mode="e2e_measured", bs=bs, ctx=ctx,
             layers=L, us_step=round(t_e2e * 1e6, 1),
             tok_s_at_depth=round(bs / t_e2e, 1),
             tpot_us=round(t_e2e * 1e6, 1),
             slope_pred_us=round(pred * 1e6, 1),
             overhead_vs_slope=round(t_e2e / max(pred, 1e-9), 3),
             overhead_decomposition=decomp,
             extrapolated=False,
             includes=["kv_append", "sampling"]),
        costmodel.serving_step(bs, ctx, L, **serve_shape), t_e2e))
    print(f"# serving e2e L={L}: {t_e2e*1e6:.1f} us/step measured "
          f"(slope model predicts {pred*1e6:.1f} us without append+sampling)",
          file=sys.stderr)


def phase_serving_fused(sweep: bool):
    """A/B: the compile-once donated-buffer fused serving step
    (``flashinfer_tpu.serve`` — ONE jitted XLA program per decode step,
    KV caches / page table / lens / PRNG key donated) vs the SAME math
    in the pre-fused dispatch structure (one jitted call per layer plus
    a jitted head+sampling epilogue, chained by a host loop — the
    per-phase micro-loop shape ``overhead_decomposition`` measured), at
    the SAME Llama-70B-shard int8 shapes as ``phase_serving``
    (BENCH_SMALL-aware).

    Both variants INCLUDE the per-step paged KV append and sampling
    (the exclusions the slope row carries do not apply here).  The
    reported number is each variant's **e2e-vs-slope overhead ratio**:
    the slope denominator is the in-jit ``lax.scan`` steady state of
    the same step (``bench_steps_device`` — zero host dispatch, the
    floor both variants share), so

        ``dispatch_residual_us = us_step - slope_pred_us``

    is exactly the per-step host tax in ``overhead_decomposition``
    residual terms, and the fused-vs-per_op residual DELTA is the tax
    the donation+fusion deletes (VERDICT weak #2's honest fix).  Rows
    carry the ``step_mode`` identity stamp so the two dispatch
    structures keep separate banked audit histories."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.serve.shard import (Int8ShardSpec, build_fused_step,
                                            build_per_op_step,
                                            head_and_sample, shard_layer)
    from flashinfer_tpu.testing import bench_steps_device
    from flashinfer_tpu.utils import is_tpu

    if os.environ.get("BENCH_SMALL"):
        bs, ctx, PS = 4, 128, 16
        hidden, hq, hkv, hd, inter, vocab_shard = 512, 4, 1, 128, 1024, 1024
        L = 2
    else:
        bs, ctx, PS = 64, 4096, 16
        hidden, hq, hkv, hd, inter, vocab_shard = 8192, 8, 1, 128, 3584, 16032
        L = 8
    spec = Int8ShardSpec(bs=bs, hidden=hidden, hq=hq, hkv=hkv, hd=hd,
                         inter=inter, vocab_shard=vocab_shard, page_size=PS,
                         use_pallas=is_tpu())
    pages_per_req = ctx // PS
    num_pages = bs * pages_per_req
    qdim, kvdim = spec.qdim, spec.kvdim
    key = jax.random.PRNGKey(0)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    ks = jax.random.split(key, 6 * L + 2)
    layer_ws = [(
        *qw(ks[6 * i], (hidden, qdim + 2 * kvdim)),
        *qw(ks[6 * i + 1], (qdim, hidden)),
        *qw(ks[6 * i + 2], (hidden, 2 * inter)),
        *qw(ks[6 * i + 3], (inter, hidden)),
        jax.random.normal(ks[6 * i + 4], (hidden,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (hidden,)) * 0.02 + 1.0,
    ) for i in range(L)]

    def mk_caches():
        return [(jax.random.randint(
                    jax.random.fold_in(ks[-2], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8),
                 jax.random.randint(
                    jax.random.fold_in(ks[-1], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8))
                for i in range(L)]

    head, head_s = qw(jax.random.fold_in(key, 999), (hidden, vocab_shard))
    pt0 = (np.random.default_rng(0).permutation(num_pages)
           .reshape(bs, pages_per_req).astype(np.int32))
    lens0 = np.full((bs,), ctx - 1, np.int32)
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (bs, hidden),
                           jnp.bfloat16)
    serve_shape = dict(hidden=hidden, hq=hq, hkv=hkv, hd=hd, inter=inter,
                       vocab_shard=vocab_shard, page_size=PS,
                       weight_bytes=1, kv_bytes=1)
    cost = costmodel.serving_step(bs, ctx, L, **serve_shape)

    # ---- the shared slope floor: the SAME step as an in-jit lax.scan
    # steady state (zero host dispatch; XLA while-body aliasing updates
    # the caches in place — the donation analogue both variants chase)
    def make_loop(n):
        @jax.jit
        def loop(x0, layer_ws, caches, head, head_s, pt, lens, skey):
            def body(carry, _):
                caches, skey = carry
                x = x0
                new_caches = []
                for w, (kcl, vcl) in zip(layer_ws, caches):
                    x, kcl, vcl = shard_layer(x, w, kcl, vcl, pt, lens,
                                              spec)
                    new_caches.append((kcl, vcl))
                tok, skey = head_and_sample(x, head, head_s, skey, spec)
                return (new_caches, skey), tok[0]
            (_, _), toks = jax.lax.scan(
                body, (caches, skey), None, length=n)
            return toks.sum()
        return loop

    t_slope = _guard(
        "bench.serving_fused.slope", (bs, ctx, L, hidden),
        lambda: bench_steps_device(
            make_loop, x0, layer_ws, mk_caches(), head, head_s,
            jnp.asarray(pt0), jnp.asarray(lens0), jax.random.PRNGKey(3),
            repeats=3,
        ),
    )
    print(f"# serving_fused slope floor: {t_slope*1e6:9.1f} us/step",
          file=sys.stderr)

    # ---- wall-clock per-step of each dispatch structure: a REAL host
    # loop (per-call dispatch included — that is the measured quantity).
    # Also times the FIRST post-warm step alone from a fresh serving
    # state: the compiled-program first-token latency — the decode-side
    # component of TTFT (prefill excluded; this bench has none), the
    # ttft_us measurement stamp on each variant's row (ISSUE 10)
    def wall(stepfn, warm=2, steps=12, repeats=3):
        best = float("inf")
        best_first = float("inf")
        for _ in range(repeats):
            caches = mk_caches()
            p = jnp.asarray(pt0)
            l = jnp.asarray(lens0)
            sk = jax.random.PRNGKey(3)
            for _ in range(warm):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # fence before the timed window
            tf0 = _time.perf_counter()
            tok, caches, p, l, sk = stepfn(
                x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # first-step fence
            best_first = min(best_first, _time.perf_counter() - tf0)
            t0 = _time.perf_counter()
            for _ in range(steps):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # execution fence (like testing/)
            best = min(best, (_time.perf_counter() - t0) / steps)
        return best, best_first

    from flashinfer_tpu import obs

    variants = (
        ("fused", build_fused_step(spec)),
        ("per_op", build_per_op_step(spec)),
    )
    residuals = {}
    for name, stepfn in variants:
        measured = _guard_soft(f"bench.serving_fused.{name}",
                               (bs, ctx, L, hidden, name),
                               lambda s=stepfn: wall(s))
        if measured is None:
            print(f"# serving_fused {name}: FAILED", file=sys.stderr)
            continue
        t, t_first = measured
        residual_us = (t - t_slope) * 1e6
        residuals[name] = residual_us
        obs.observe("lifecycle.tpot_us", t * 1e6)
        obs.observe("lifecycle.ttft_us", t_first * 1e6)
        # host/device overlap probe (ISSUE 17): its own short window so
        # the per-step sync never taxes the us_step throughput number
        overlap = _guard_soft(
            f"bench.serving_fused.{name}.overlap",
            (bs, ctx, L, hidden, name),
            lambda s=stepfn: _probed_overlap(
                s, x0, layer_ws, mk_caches(), head, head_s,
                jnp.asarray(pt0), jnp.asarray(lens0),
                jax.random.PRNGKey(3))) or {}
        _emit_row(**_stamp(
            dict(phase="serving_fused", model="llama70b_tp8shard_int8",
                 variant=name, bs=bs, ctx=ctx, layers=L,
                 us_step=round(t * 1e6, 1),
                 # lifecycle stamps (measurement fields): steady-state
                 # per-token latency + compiled first-token latency
                 # from a fresh state (decode-side TTFT; no prefill
                 # exists in this loop)
                 tpot_us=round(t * 1e6, 1),
                 ttft_us=round(t_first * 1e6, 1),
                 slope_pred_us=round(t_slope * 1e6, 1),
                 overhead_vs_slope=round(t / max(t_slope, 1e-9), 3),
                 dispatch_residual_us=round(residual_us, 1),
                 pred_step_ratio=_pred_step_ratio(cost, t),
                 includes=["kv_append", "sampling"], **overlap),
            cost, t, step_mode=name))
        print(f"# serving_fused {name:7s}: {t*1e6:9.1f} us/step "
              f"({t/max(t_slope,1e-9):.3f}x slope, residual "
              f"{residual_us:+.1f} us, first-step {t_first*1e6:.1f} us)",
              file=sys.stderr)
    if len(residuals) == 2:
        delta = residuals["per_op"] - residuals["fused"]
        print(f"# serving_fused dispatch residual delta (per_op - fused): "
              f"{delta:+.1f} us/step", file=sys.stderr)


def phase_serving_sharded(sweep: bool):
    """A/B: the compile-once SHARDED serving step (``parallel/plan.py``
    — GLOBAL 70B dims compiled ONCE under a (dp, tp) mesh with explicit
    NamedShardings + donated KV state: one XLA program per step for the
    WHOLE mesh) vs the SAME sharded math as per-layer jitted calls
    chained by a host loop (the pre-fused dispatch structure, now with
    ``layers + 1`` collective-bearing dispatches per step).

    The slope denominator is the in-jit ``lax.scan`` steady state of
    the same sharded body (zero host dispatch), so
    ``dispatch_residual_us = us_step - slope_pred_us`` is the per-step
    host tax each dispatch structure pays ON A MESH — the multi-chip
    sequel to ``phase_serving_fused``'s single-chip A/B.

    Rows carry BOTH identity stamps: ``step_mode`` (fused | per_op) and
    ``mesh_axes`` (``ShardingPlan.mesh_axes``, e.g. "dp1.tp8") — a tp8
    row must never compete with tp1 history — plus the new ICI
    measurement fields (``ici_bytes`` / ``pct_ici_roofline``) from the
    collective cost family.

    CPU-mesh dryrun-capable: under BENCH_SMALL with no initialized
    backend the phase forces an 8-virtual-device host platform, so the
    whole A/B (compile-once, donation, collectives) runs off-hardware;
    the timings are then structural, not performance claims — the
    predicted multi-chip story is ``obs perf``'s scaling curve."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.parallel.plan import (build_sharded_fused_step,
                                              build_sharded_per_op_step,
                                              make_serving_mesh,
                                              sharded_step_body,
                                              split_shard_weights_for_spec,
                                              validate_dp_page_table)
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.serve.shard import Int8ShardSpec
    from flashinfer_tpu.testing import bench_steps_device
    from flashinfer_tpu.utils import is_tpu

    if os.environ.get("BENCH_SMALL"):
        bs, ctx, PS = 4, 128, 16
        hidden, hq, hkv, hd, inter, vocab = 512, 8, 4, 128, 1024, 1024
        L = 2
    else:
        # GLOBAL Llama-3-70B dims (the whole model — the plan shards it;
        # tp8 of this is exactly phase_serving's per-chip shard)
        bs, ctx, PS = 64, 4096, 16
        hidden, hq, hkv, hd, inter, vocab = 8192, 64, 8, 128, 28672, 128256
        L = 8
    plan = make_serving_mesh(hidden=hidden, num_qo_heads=hq,
                             num_kv_heads=hkv)
    print(f"# serving_sharded mesh: {plan.mesh_axes} over "
          f"{len(jax.devices())} device(s)", file=sys.stderr)
    spec = Int8ShardSpec(bs=bs, hidden=hidden, hq=hq, hkv=hkv, hd=hd,
                         inter=inter, vocab_shard=vocab, page_size=PS,
                         use_pallas=is_tpu())
    pages_per_req = ctx // PS
    num_pages = bs * pages_per_req
    qdim, kvdim = spec.qdim, spec.kvdim
    key = jax.random.PRNGKey(0)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    ks = jax.random.split(key, 6 * L + 2)
    layer_ws = split_shard_weights_for_spec([(
        *qw(ks[6 * i], (hidden, qdim + 2 * kvdim)),
        *qw(ks[6 * i + 1], (qdim, hidden)),
        *qw(ks[6 * i + 2], (hidden, 2 * inter)),
        *qw(ks[6 * i + 3], (inter, hidden)),
        jax.random.normal(ks[6 * i + 4], (hidden,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (hidden,)) * 0.02 + 1.0,
    ) for i in range(L)], spec)

    def mk_caches():
        return [(jax.random.randint(
                    jax.random.fold_in(ks[-2], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8),
                 jax.random.randint(
                    jax.random.fold_in(ks[-1], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8))
                for i in range(L)]

    head, head_s = qw(jax.random.fold_in(key, 999), (hidden, vocab))
    # DP page-pool contract: request b's pages come from its dp slab
    bs_l = bs // plan.dp_size
    pages_l = num_pages // plan.dp_size
    rng = np.random.default_rng(0)
    pt0 = np.stack([
        rng.permutation(pages_l)[:pages_per_req]
        + (b // bs_l) * pages_l
        for b in range(bs)]).astype(np.int32)
    validate_dp_page_table(pt0, num_pages, plan)
    lens0 = np.full((bs,), ctx - 1, np.int32)
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (bs, hidden),
                           jnp.bfloat16)
    shape = dict(hidden=hidden, hq=hq, hkv=hkv, hd=hd, inter=inter,
                 vocab_shard=vocab, page_size=PS, weight_bytes=1,
                 kv_bytes=1)
    # PER-CHIP cost on this mesh, collective ICI bytes included
    cost = costmodel.serving_step_sharded(
        bs, ctx, L, dp=plan.dp_size, tp=plan.tp_size, **shape)

    # ---- shared slope floor: the SAME sharded step as an in-jit
    # lax.scan steady state (zero host dispatch)
    body = sharded_step_body(spec, plan)

    def make_loop(n):
        @jax.jit
        def loop(x0, layer_ws, caches, head, head_s, pt, lens, skey):
            def scan_body(carry, _):
                caches, skey = carry
                tok, caches, _, _, skey = body(
                    x0, layer_ws, caches, head, head_s, pt, lens, skey)
                return (caches, skey), tok[0]
            (_, _), toks = jax.lax.scan(
                scan_body, (caches, skey), None, length=n)
            return toks.sum()
        return loop

    t_slope = _guard(
        "bench.serving_sharded.slope",
        (bs, ctx, L, hidden, plan.mesh_axes),
        lambda: bench_steps_device(
            make_loop, x0, layer_ws, mk_caches(), head, head_s,
            jnp.asarray(pt0), jnp.asarray(lens0), jax.random.PRNGKey(3),
            repeats=3,
        ),
    )
    print(f"# serving_sharded slope floor: {t_slope*1e6:9.1f} us/step",
          file=sys.stderr)

    # first post-warm step timed alone from a fresh state: the mesh
    # program's first-token latency — the decode-side ttft_us stamp
    # (same protocol as phase_serving_fused's wall())
    def wall(stepfn, warm=2, steps=12, repeats=3):
        best = float("inf")
        best_first = float("inf")
        for _ in range(repeats):
            caches = mk_caches()
            p = jnp.asarray(pt0)
            l = jnp.asarray(lens0)
            sk = jax.random.PRNGKey(3)
            for _ in range(warm):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # fence before the timed window
            tf0 = _time.perf_counter()
            tok, caches, p, l, sk = stepfn(
                x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # first-step fence
            best_first = min(best_first, _time.perf_counter() - tf0)
            t0 = _time.perf_counter()
            for _ in range(steps):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])  # execution fence
            best = min(best, (_time.perf_counter() - t0) / steps)
        return best, best_first

    fused = build_sharded_fused_step(spec, plan, num_layers=L)
    variants = (
        ("fused", fused),
        ("per_op", build_sharded_per_op_step(spec, plan)),
    )
    residuals = {}
    for name, stepfn in variants:
        measured = _guard_soft(f"bench.serving_sharded.{name}",
                               (bs, ctx, L, hidden, plan.mesh_axes, name),
                               lambda s=stepfn: wall(s))
        if measured is None:
            print(f"# serving_sharded {name}: FAILED", file=sys.stderr)
            continue
        t, t_first = measured
        residual_us = (t - t_slope) * 1e6
        residuals[name] = residual_us
        # host/device overlap probe (ISSUE 17): separate window, the
        # serving_fused protocol, on the mesh program
        overlap = _guard_soft(
            f"bench.serving_sharded.{name}.overlap",
            (bs, ctx, L, hidden, plan.mesh_axes, name),
            lambda s=stepfn: _probed_overlap(
                s, x0, layer_ws, mk_caches(), head, head_s,
                jnp.asarray(pt0), jnp.asarray(lens0),
                jax.random.PRNGKey(3))) or {}
        _emit_row(**_stamp(
            dict(phase="serving_sharded", model="llama70b_int8",
                 variant=name, bs=bs, ctx=ctx, layers=L,
                 us_step=round(t * 1e6, 1),
                 tpot_us=round(t * 1e6, 1),
                 ttft_us=round(t_first * 1e6, 1),
                 slope_pred_us=round(t_slope * 1e6, 1),
                 overhead_vs_slope=round(t / max(t_slope, 1e-9), 3),
                 dispatch_residual_us=round(residual_us, 1),
                 pred_step_ratio=_pred_step_ratio(cost, t, ici=True),
                 includes=["kv_append", "sampling", "collectives"],
                 **overlap),
            cost, t, step_mode=name, mesh_axes=plan.mesh_axes))
        print(f"# serving_sharded {name:7s}: {t*1e6:9.1f} us/step "
              f"({t/max(t_slope,1e-9):.3f}x slope, residual "
              f"{residual_us:+.1f} us, first-step {t_first*1e6:.1f} us)",
              file=sys.stderr)
    if fused.num_traces != 1:
        print(f"# serving_sharded WARNING: fused step traced "
              f"{fused.num_traces}x (compile-once broke)", file=sys.stderr)
    if len(residuals) == 2:
        delta = residuals["per_op"] - residuals["fused"]
        print(f"# serving_sharded dispatch residual delta "
              f"(per_op - fused): {delta:+.1f} us/step", file=sys.stderr)


def phase_serving_engine(sweep: bool):
    """Continuous-batching serving ENGINE (``serve/engine.py``): 1000+
    synthetic requests with Zipf-skewed shared prefixes driven through
    the block pool + prefix trie + SLO scheduler on the compile-once
    rung ladder.

    What the row proves (all CPU-provable — this phase measures ENGINE
    mechanics: scheduling, prefix reuse, retrace discipline; kernel
    throughput has its own phases):

    - span-layer TTFT/TPOT p50/p99 stamped from the PR 10 lifecycle
      histograms (requests metered begin -> prefill chunks -> decode
      steps -> finish);
    - measured prefix-cache hit rate > 0 with the avoided prefill
      FLOPs priced by ``costmodel.engine_step``;
    - the whole run stays on the pre-compiled rung ladder (<= the
      9-trace budget ``obs trace --selftest`` pins);
    - engine tokens BITWISE-EQUAL to the no-sharing oracle (the same
      requests, full per-request prefill) — the phase RAISES on any
      mismatch, so a divergent row can never land.

    Backend A/B (ISSUE 12): the phase emits PAIRED rows — the same
    shared-prefix workload served by ``attention_backend="reference"``
    (the dense XLA oracle tier) and by ``attention_backend="kernel"``
    (the Pallas work-unit lowering, interpret-mode on CPU) — stamped
    with ``attention_backend`` as a RowAuditor IDENTITY field so the
    tiers keep separate banked histories.  The kernel row's cost comes
    from the REAL unit stats (``ServingEngine.unit_stats`` →
    ``costmodel.engine_step`` launched-vs-effective), its
    ``prefill_flops_avoided`` is planner-derived (unit stats for the
    skipped spans), and cross-tier token agreement is GATED by model
    dtype: on f32 models (BENCH_SMALL) the tiers agree exactly (the
    tests/test_engine_kernels.py contract) and the phase raises on
    >0.2% drift; on bf16 models the kernel tier's whole point is bf16
    MXU dots where the reference upcasts to f32, and one benign token
    flip diverges the rest of that request's sequence — so the phase
    only records the WHOLE-REQUEST agreement rate
    (``backend_token_match``) and never gates on it (lowering bugs
    are caught exactly by the f32 interpret tier).
    On CPU the kernel row's wall time measures INTERPRET-mode
    emulation, not kernel speed: read the A/B for plan mechanics +
    parity here, for throughput on chip (BENCH_BANKED.md note).

    The roofline stamp uses the run-aggregate ``engine_step`` cost
    (shared-prefix KV reads deduped via kv_rows), so ``obs perf``
    attributes the cascade win mechanically."""
    import time as _time

    os.environ["FLASHINFER_TPU_SPANS"] = "1"
    os.environ["FLASHINFER_TPU_METRICS"] = "1"
    # step-loop flight deck ON for the run (ISSUE 17): the engine's
    # step() is wired, so the ledger prices every dispatch — the probe
    # tax is part of this phase's measured quantity (phases run in
    # their own subprocess, the gate never leaks)
    os.environ["FLASHINFER_TPU_STEPLOOP"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu import obs
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.obs import steploop
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    if os.environ.get("BENCH_SMALL"):
        n_requests, n_prefixes = 1000, 32
        prefix_len, suffix_hi, max_new = 24, 8, 4
        mcfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
        ecfg_kw = dict(num_pages=257, page_size=8, max_batch=8,
                       prefill_budget_tokens=32, max_seq_tokens=64)
    else:
        n_requests, n_prefixes = 2000, 64
        prefix_len, suffix_hi, max_new = 96, 16, 8
        mcfg = LlamaConfig.tiny(num_layers=4, hidden_size=512,
                                intermediate_size=1024)
        ecfg_kw = dict(num_pages=1025, page_size=16, max_batch=16,
                       prefill_budget_tokens=128, max_seq_tokens=192)
    ecfg_kw["sampling"] = SamplingConfig(temperature=0.8, top_k=40)
    params = init_llama_params(jax.random.PRNGKey(0), mcfg)

    def workload():
        rng = np.random.default_rng(7)
        prefixes = [[int(t) for t in
                     rng.integers(1, mcfg.vocab_size, prefix_len)]
                    for _ in range(n_prefixes)]
        ranks = np.minimum(rng.zipf(1.2, n_requests) - 1, n_prefixes - 1)
        reqs = []
        for i in range(n_requests):
            suffix = [int(t) for t in rng.integers(
                1, mcfg.vocab_size, int(rng.integers(1, suffix_hi + 1)))]
            reqs.append((f"req{i}", prefixes[int(ranks[i])] + suffix))
        return reqs

    def serve(share: bool, backend: str = "reference"):
        eng = ServingEngine(mcfg, params, EngineConfig(
            enable_prefix_cache=share, attention_backend=backend,
            **ecfg_kw))
        for rid, prompt in workload():
            eng.submit(EngineRequest(rid, list(prompt),
                                     max_new_tokens=max_new))
        t0 = _time.perf_counter()
        tag = "share" if share else "oracle"
        results = _guard(f"bench.serving_engine.{tag}.{backend}",
                         (n_requests, mcfg.hidden_size, share, backend),
                         lambda: eng.run())
        return results, _time.perf_counter() - t0, eng

    obs.reset()
    steploop.reset()
    results, wall, eng = serve(True)
    sl = steploop.summarize()  # before the oracle run pollutes it
    snap = obs.snapshot()
    ls = obs.lifecycle_snapshot()
    hits = sum(snap["counters"].get("engine.prefix_hit_tokens",
                                    {}).values())
    misses = sum(snap["counters"].get("engine.prefix_miss_tokens",
                                      {}).values())
    hit_rate = hits / max(hits + misses, 1)
    gen_tokens = sum(len(v) for v in results.values())

    # the no-sharing oracle: full per-request prefill, same requests.
    # Bitwise token equality is the engine's correctness contract
    # (docs/serving.md) — a mismatch aborts the phase before any row.
    oracle_results, oracle_wall, oracle_eng = serve(False)
    if oracle_results != results:
        bad = [rid for rid in results
               if results[rid] != oracle_results.get(rid)]
        raise AssertionError(
            f"engine-vs-oracle token mismatch on {len(bad)} of "
            f"{n_requests} requests (first: {bad[:3]}) — the shared-"
            "prefix cascade path diverged from full prefill")
    if eng.num_traces > 9:
        raise AssertionError(
            f"retrace budget breached: {eng.num_traces} traces "
            f"across {eng.steps} engine steps (budget: 9)")

    def engine_row(e, w, ls_, snap_, hit_rate_, gen_tokens_):
        def pct(name, p):
            h = ls_.get(name) or {}
            return round(h.get(p, 0.0), 1)

        return dict(
            phase="serving_engine", model="llama_tiny_engine",
            requests=n_requests, zipf_prefixes=n_prefixes,
            bs=ecfg_kw["max_batch"], page_size=ecfg_kw["page_size"],
            prefill_budget=ecfg_kw["prefill_budget_tokens"],
            layers=mcfg.num_layers, hidden=mcfg.hidden_size,
            gen_tokens=gen_tokens_, engine_steps=e.steps,
            us_step=round(w / max(e.steps, 1) * 1e6, 1),
            tok_s=round(gen_tokens_ / max(w, 1e-9), 1),
            ttft_p50_us=pct("lifecycle.ttft_us", "p50"),
            ttft_p99_us=pct("lifecycle.ttft_us", "p99"),
            tpot_p50_us=pct("lifecycle.tpot_us", "p50"),
            tpot_p99_us=pct("lifecycle.tpot_us", "p99"),
            prefix_hit_rate=round(hit_rate_, 4),
            prefill_flops_avoided=e.flops_avoided,
            num_traces=e.num_traces,
            preemptions=sum(
                snap_["counters"].get("engine.preemptions", {}).values()),
            evictions=sum(
                snap_["counters"].get("engine.evictions", {}).values()),
        )

    row = engine_row(eng, wall, ls, snap, hit_rate, gen_tokens)
    row["oracle"] = "tokens-bitwise-equal"
    row["oracle_speedup"] = round(oracle_wall / max(wall, 1e-9), 3)
    # steploop ledger stamps: real host-gap decomposition + the online
    # predicted-vs-measured drift join (the engine prices its steps)
    row.update(_host_loop_stamps(sl))
    _emit_row(**_stamp(row, eng.aggregate_cost(), wall,
                       attention_backend="reference"))
    print(f"# serving_engine: {n_requests} reqs in {wall:.1f}s "
          f"({row['tok_s']} tok/s), hit rate {hit_rate:.1%}, "
          f"{eng.num_traces} traces/{eng.steps} steps, "
          f"host_frac {row.get('host_frac', 'n/a')}, "
          f"oracle bitwise OK ({oracle_wall:.1f}s unshared, "
          f"{row['oracle_speedup']}x)", file=sys.stderr)

    # ---- kernel-tier A/B (ISSUE 12): same workload, Pallas work-unit
    # attention; on CPU this measures interpret-mode mechanics, the
    # throughput half of the A/B is the first on-chip session's
    obs.reset()
    steploop.reset()
    kresults, kwall, keng = serve(True, backend="kernel")
    ksl = steploop.summarize()
    ksnap = obs.snapshot()
    kls = obs.lifecycle_snapshot()
    khits = sum(ksnap["counters"].get("engine.prefix_hit_tokens",
                                      {}).values())
    kmisses = sum(ksnap["counters"].get("engine.prefix_miss_tokens",
                                        {}).values())
    match = sum(1 for rid in results
                if kresults.get(rid) == results[rid])
    # f32 models: exact agreement is the pinned contract (0.2% slack
    # for a knife-edge argmax flip).  bf16 models: the kernel tier
    # computes bf16 MXU dots where the reference upcasts to f32, and
    # ONE benign token flip diverges the rest of that request's
    # sequence, so WHOLE-REQUEST agreement can legitimately land
    # anywhere below 1.0 — record the rate, never raise (the f32
    # interpret tier is where lowering bugs are caught exactly)
    strict = mcfg.dtype == jnp.float32
    if strict and match < n_requests * 0.998:
        bad = [rid for rid in results
               if kresults.get(rid) != results[rid]]
        raise AssertionError(
            f"kernel-vs-reference token mismatch on {len(bad)} of "
            f"{n_requests} requests (first: {bad[:3]}) — the work-unit "
            "lowering diverged from the oracle tier")
    if keng.num_traces > 9:
        raise AssertionError(
            f"kernel-tier retrace budget breached: {keng.num_traces} "
            f"traces across {keng.steps} engine steps (budget: 9)")
    kgen = sum(len(v) for v in kresults.values())
    krow = engine_row(keng, kwall, kls, ksnap,
                      khits / max(khits + kmisses, 1), kgen)
    krow["backend_tokens_equal"] = bool(match == n_requests)
    krow["backend_token_match"] = round(match / max(n_requests, 1), 4)
    krow.update(_host_loop_stamps(ksl))
    kcost = keng.aggregate_cost()
    _emit_row(**_stamp(krow, kcost, kwall, attention_backend="kernel"))
    us = keng.unit_stats
    print(f"# serving_engine[kernel]: {kwall:.1f}s interpret-mode, "
          f"{keng.num_traces} traces/{keng.steps} steps, tokens "
          f"{'EQUAL' if match == n_requests else f'{match}/{n_requests}'}"
          f" vs reference; launched/effective flops "
          f"{kcost.flops:.3g}/{kcost.effective_flops:.3g} "
          f"({us['prefill_units']} real prefill units of "
          f"{us['prefill_units_launched']} launched)", file=sys.stderr)


def phase_serving_disagg(sweep: bool):
    """Tiered-KV subsystem (``serve/kv_tier.py``): the disaggregated
    prefill→decode handoff and the host-RAM spill tier, both proven
    on CPU and priced by the cost model (the PR 8 before-hardware
    pattern).  Three row modes (``mode`` is RowAuditor identity —
    separate banked histories):

    - ``handoff``: the same shared-prefix workload served UNIFIED vs
      DISAGGREGATED (prefill pool + decode pool joined by
      ``kv_migrate``); the phase RAISES on any token mismatch, then
      stamps the disagg row with both pools' engine_step cost PLUS the
      summed ``kv_migrate`` cost — migration count/bytes/wall ride as
      measurement fields, ``ici_bytes`` lands on the stamp.
    - ``kv_migrate``: the handoff traffic alone attributed over its
      measured host-copy wall — ``bound == "ici"`` by construction
      (the wire floor is the deepest on every registered chip), the
      migration row the ISSUE asks ``roofline.stamp_row`` to surface.
      On CPU the "measured" time is a host memcpy (interpret-mode
      caveat: read the predicted-vs-measured join in ``obs perf``
      serving_disagg for mechanics, on-chip wire time pending).
    - ``spill``: a pool SMALLER than the working set under
      ``spill_policy="spill"`` — effective KV capacity beyond the
      device budget.  The phase raises unless the run completes with
      ZERO recomputes and tokens bitwise-equal to the big-pool
      never-preempted oracle (the restore-path contract)."""
    import time as _time

    os.environ["FLASHINFER_TPU_SPANS"] = "1"
    os.environ["FLASHINFER_TPU_METRICS"] = "1"
    # step-loop flight deck ON (the serving_engine rule): both pools'
    # engines are wired, so the disagg rows carry real host-gap stamps
    os.environ["FLASHINFER_TPU_STEPLOOP"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.obs import steploop
    from flashinfer_tpu.serve import (DisaggServing, EngineConfig,
                                      EngineRequest, SamplingConfig,
                                      ServingEngine)

    if os.environ.get("BENCH_SMALL"):
        n_requests, n_prefixes = 120, 8
        prefix_len, suffix_hi, max_new = 24, 8, 4
        mcfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
        ecfg_kw = dict(num_pages=129, page_size=8, max_batch=4,
                       prefill_budget_tokens=32, max_seq_tokens=64)
    else:
        n_requests, n_prefixes = 400, 16
        prefix_len, suffix_hi, max_new = 48, 16, 6
        mcfg = LlamaConfig.tiny(num_layers=4, hidden_size=512,
                                intermediate_size=1024,
                                dtype=jnp.float32)
        ecfg_kw = dict(num_pages=513, page_size=16, max_batch=8,
                       prefill_budget_tokens=64, max_seq_tokens=128)
    ecfg_kw["sampling"] = SamplingConfig(temperature=0.8, top_k=40)
    params = init_llama_params(jax.random.PRNGKey(0), mcfg)

    def workload():
        rng = np.random.default_rng(17)
        prefixes = [[int(t) for t in
                     rng.integers(1, mcfg.vocab_size, prefix_len)]
                    for _ in range(n_prefixes)]
        ranks = np.minimum(rng.zipf(1.2, n_requests) - 1, n_prefixes - 1)
        reqs = []
        for i in range(n_requests):
            suffix = [int(t) for t in rng.integers(
                1, mcfg.vocab_size, int(rng.integers(1, suffix_hi + 1)))]
            reqs.append((f"req{i}", prefixes[int(ranks[i])] + suffix))
        return reqs

    # ---- leg 1+2: unified vs disaggregated (the handoff A/B) ----------
    eng = ServingEngine(mcfg, params, EngineConfig(**ecfg_kw))
    for rid, prompt in workload():
        eng.submit(EngineRequest(rid, list(prompt),
                                 max_new_tokens=max_new))
    t0 = _time.perf_counter()
    uni = _guard("bench.serving_disagg.unified",
                 (n_requests, mcfg.hidden_size),
                 lambda: eng.run())
    uni_wall = _time.perf_counter() - t0

    disagg = DisaggServing(mcfg, params, EngineConfig(**ecfg_kw))
    for rid, prompt in workload():
        disagg.submit(EngineRequest(rid, list(prompt),
                                    max_new_tokens=max_new))
    steploop.reset()  # the handoff row's ledger window: disagg only
    t0 = _time.perf_counter()
    dis = _guard("bench.serving_disagg.disagg",
                 (n_requests, mcfg.hidden_size),
                 lambda: disagg.run())
    dis_wall = _time.perf_counter() - t0
    dsl = steploop.summarize()
    if dis != uni:
        bad = [rid for rid in uni if dis.get(rid) != uni[rid]]
        raise AssertionError(
            f"disagg-vs-unified token mismatch on {len(bad)} of "
            f"{n_requests} requests (first: {bad[:3]}) — the "
            "prefill→decode handoff diverged from the unified engine")
    for e, tag in ((disagg.prefill, "prefill"), (disagg.decode,
                                                 "decode")):
        if e.num_traces > 9:
            raise AssertionError(
                f"disagg {tag}-pool retrace budget breached: "
                f"{e.num_traces} traces (budget: 9)")
    ms = disagg.migration_stats
    gen_tokens = sum(len(v) for v in dis.values())
    row = dict(
        phase="serving_disagg", mode="handoff",
        model="llama_tiny_engine", requests=n_requests,
        zipf_prefixes=n_prefixes, bs=ecfg_kw["max_batch"],
        page_size=ecfg_kw["page_size"], layers=mcfg.num_layers,
        hidden=mcfg.hidden_size, gen_tokens=gen_tokens,
        tok_s=round(gen_tokens / max(dis_wall, 1e-9), 1),
        migrations=int(ms["migrations"]),
        migrate_bytes=float(ms["bytes"]),
        migrate_us=round(ms["seconds"] * 1e6, 1),
        disagg_tokens_equal=True,
        unified_wall_s=round(uni_wall, 2),
        **_host_loop_stamps(dsl),
    )
    _emit_row(**_stamp(row, disagg.aggregate_cost(), dis_wall))
    print(f"# serving_disagg handoff: {n_requests} reqs, tokens "
          f"BITWISE == unified ({uni_wall:.1f}s unified / "
          f"{dis_wall:.1f}s disagg), {row['migrations']} migrations "
          f"{row['migrate_bytes'] / 1e6:.1f} MB", file=sys.stderr)

    # the migration traffic alone: the ici-bound handoff row
    if disagg._migration_cost is not None and ms["seconds"] > 0:
        mrow = dict(
            phase="serving_disagg", mode="kv_migrate",
            model="llama_tiny_engine", requests=n_requests,
            page_size=ecfg_kw["page_size"], layers=mcfg.num_layers,
            hidden=mcfg.hidden_size,
            migrations=int(ms["migrations"]),
            migrate_bytes=float(ms["bytes"]),
            migrate_us=round(ms["seconds"] * 1e6, 1),
        )
        _emit_row(**_stamp(mrow, disagg._migration_cost,
                           ms["seconds"]))
        print(f"# serving_disagg kv_migrate: "
              f"{mrow['migrate_bytes'] / 1e6:.1f} MB in "
              f"{ms['seconds'] * 1e3:.1f} ms host-copy "
              f"(bound={mrow['bound']}, interpret-mode wall — wire "
              f"proof pending on chip)", file=sys.stderr)

    # ---- leg 3: host-RAM spill raises effective capacity --------------
    def serve_spill(npages, **tier):
        eng = ServingEngine(mcfg, params, EngineConfig(
            **{**ecfg_kw, "num_pages": npages, "max_batch": 2}, **tier))
        rng = np.random.default_rng(29)
        prompts = [[int(t) for t in rng.integers(
            1, mcfg.vocab_size, prefix_len)] for _ in range(8)]
        for i, p in enumerate(prompts):
            eng.submit(EngineRequest(f"s{i}", list(p),
                                     max_new_tokens=max_new,
                                     priority=5))
        for _ in range(4):
            eng.step()
        for i, p in enumerate(prompts[:4]):
            eng.submit(EngineRequest(f"hi{i}", list(p[::-1]),
                                     max_new_tokens=max_new,
                                     priority=0))
        t0 = _time.perf_counter()
        res = eng.run()
        return res, _time.perf_counter() - t0, eng

    # small pool: fewer pages than the 12-request working set needs
    small_pages = 4 * (-(-(prefix_len + max_new)
                         // ecfg_kw["page_size"])) + 1
    oracle_res, _, _ = serve_spill(ecfg_kw["num_pages"])
    steploop.reset()  # the spill row's ledger window
    spill_res, spill_wall, seng = _guard(
        "bench.serving_disagg.spill", (small_pages, mcfg.hidden_size),
        lambda: serve_spill(small_pages, kv_offload="host",
                            spill_policy="spill", host_gib=1))
    ssl = steploop.summarize()
    st = seng.kv_tier_stats
    if spill_res != oracle_res:
        bad = [rid for rid in oracle_res
               if spill_res.get(rid) != oracle_res[rid]]
        raise AssertionError(
            f"spill-restore token mismatch on {len(bad)} requests "
            f"(first: {bad[:3]}) — the restore path is not bit-exact")
    if st["spills"] == 0:
        raise AssertionError(
            "capacity-pressure run never spilled — the pool was not "
            "smaller than the working set, the capacity claim is "
            "unproven")
    if st["recomputes"] != 0:
        raise AssertionError(
            f"{st['recomputes']} resumes RECOMPUTED under "
            "spill_policy=spill — the host tier dropped entries")
    from flashinfer_tpu.obs import costmodel

    io_pages = int(st["spill_bytes"]
                   / max(costmodel.kv_page_bytes(
                       1, page_size=ecfg_kw["page_size"],
                       num_kv_heads=mcfg.num_kv_heads,
                       head_dim=mcfg.head_dim,
                       layers=mcfg.num_layers, kv_bytes=4), 1))
    srow = dict(
        phase="serving_disagg", mode="spill",
        model="llama_tiny_engine", pool_pages=small_pages,
        page_size=ecfg_kw["page_size"], layers=mcfg.num_layers,
        hidden=mcfg.hidden_size,
        spills=int(st["spills"]), restores=int(st["restores"]),
        spill_bytes=float(st["spill_bytes"]),
        restore_bytes=float(st["restore_bytes"]),
        recomputes=int(st["recomputes"]),
        host_evictions=int(seng.host_store.evictions),
        spill_tokens_equal=True,
        tok_s=round(sum(len(v) for v in spill_res.values())
                    / max(spill_wall, 1e-9), 1),
        **_host_loop_stamps(ssl),
    )
    _emit_row(**_stamp(srow, seng.aggregate_cost(), spill_wall))
    print(f"# serving_disagg spill: pool {small_pages} pages < working "
          f"set, {srow['spills']} spills/{srow['restores']} restores "
          f"({io_pages} page-spills), ZERO recomputes, tokens BITWISE "
          f"== big-pool oracle", file=sys.stderr)


def phase_selftest(sweep: bool):
    """Orchestration self-test: emits rows then hangs (no TPU touched) —
    lets CI assert that a hung phase still yields its landed rows."""
    _emit_row(phase="selftest", n=1)
    _emit_row(phase="selftest", n=2)
    if os.environ.get("BENCH_SELFTEST_HANG"):
        time.sleep(600)


PHASES = {
    "decode": phase_decode,
    "decode_splits": phase_decode_splits,
    "sampling": phase_sampling,
    "moe": phase_moe,
    "topk": phase_topk,
    "scans": phase_scans,
    "serving": phase_serving,
    "serving_fused": phase_serving_fused,
    "serving_sharded": phase_serving_sharded,
    "serving_engine": phase_serving_engine,
    "serving_disagg": phase_serving_disagg,
    "prefill": phase_prefill,
    "mla": phase_mla,
    "selftest": phase_selftest,
}
# selftest is CI-only (reachable via --only); production runs must not
# spawn the stub or bank its rows
#   decode first (the official headline metric), serving second (the
#   BASELINE.md north star) — a mid-run wedge in a later phase must not
#   cost either deliverable
#   decode/serving first (deliverables), then the hardware-proven phase
#   set, then the two phases whose BENCH rows have never run on chip
#   (prefill, mla — kernels hw-proven in the tier, the bench drivers
#   aren't): a first-run failure there must not cost any proven row
#   decode_splits rides after the proven set: its kernel is
#   interpret-proven but has never run on chip (split path committed,
#   on-chip proof pending — PARITY.md), so a first-run failure there
#   must not cost a proven row
#   serving_fused rides LAST (after decode_splits): the fused-step A/B
#   has never run on chip, and the headline serving rows above keep
#   their banked identity (the fused rows carry step_mode so they can
#   never shadow the per-phase history)
#   serving_sharded rides after serving_fused (the very end): it is the
#   first phase that occupies EVERY chip of a mesh, so a wedge there
#   must cost nothing else; rows carry mesh_axes identity so they can
#   never shadow single-chip history
#   serving_engine rides at the very end: it is a host-scheduling +
#   reuse proof (CPU-provable mechanics), so a failure there must not
#   cost any kernel-throughput row; its rows carry the engine config
#   as identity and lifecycle/hit-rate fields as measurements
#   serving_disagg rides after serving_engine (the tail of the tail):
#   the tiered-KV proof is also CPU-provable mechanics (handoff
#   bitwise parity, spill capacity, migration pricing) and its rows
#   carry mode identity so they can never shadow engine history
DEFAULT_PHASES = ["decode", "serving", "sampling", "moe", "topk", "scans",
                  "prefill", "mla", "decode_splits", "serving_fused",
                  "serving_sharded", "serving_engine", "serving_disagg"]


# --------------------------------------------------------------------------
# Orchestrator
# --------------------------------------------------------------------------


def _run_phase(name: str, sweep: bool, timeout_s: float):
    """Run one phase in a subprocess; return (rows, ok, detail)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    if sweep:
        cmd.append("--sweep")
    rows, ok, detail = [], False, ""
    t0 = time.time()
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        # incremental read: rows printed before a hang are kept
        import threading

        def pump():
            for line in p.stdout:
                if line.startswith("ROW "):
                    try:
                        rows.append(json.loads(line[4:]))
                    except json.JSONDecodeError:
                        pass

        def pump_err():
            for line in p.stderr:
                sys.stderr.write(line)

        th = threading.Thread(target=pump, daemon=True)
        te = threading.Thread(target=pump_err, daemon=True)
        th.start()
        te.start()
        p.wait(timeout=timeout_s)
        th.join(timeout=10)
        te.join(timeout=10)
        ok = p.returncode == 0
        detail = f"rc={p.returncode}"
    except subprocess.TimeoutExpired:
        p.kill()
        try:
            p.wait(timeout=10)
        except Exception:
            pass
        # after kill the pipe EOFs: a short join drains ROW lines that were
        # buffered when the phase hung — the salvage guarantee
        th.join(timeout=10)
        te.join(timeout=10)
        detail = f"timed out after {timeout_s:.0f}s (chip wedged?)"
    print(f"# phase {name}: {len(rows)} rows, {detail}, "
          f"{time.time() - t0:.0f}s", file=sys.stderr)
    return rows, ok, detail


def _bank(record: dict) -> None:
    stamp = datetime.datetime.now().isoformat(timespec="seconds")
    lines = [f"\n## {stamp} — bench.py run\n", "```json"]
    lines.append(json.dumps(record, indent=1))
    lines.append("```")
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_BANKED.md"), "a") as fh:
        fh.write("\n".join(lines) + "\n")


def orchestrate(sweep: bool, bank: bool, phases=None, no_probe=False) -> int:
    from flashinfer_tpu import compile_guard

    wedged = False
    all_rows = []
    if no_probe:
        probe = {"healthy": True, "detail": "skipped (--no-probe)"}
    else:
        probe = compile_guard.probe(timeout_s=PROBE_TIMEOUT_S)
    print(f"# probe: {probe}", file=sys.stderr)
    # bring-up quarantine (ISSUE 20): phases a wedge-attributed smoke
    # rung names are skipped, not re-dispatched into the same wedge
    try:
        from flashinfer_tpu.obs import bringup

        poisoned = set(bringup.quarantined_bench_phases())
    except Exception:
        bringup, poisoned = None, set()
    if probe["healthy"]:
        todo = list(phases or DEFAULT_PHASES)
        while todo:
            name = todo.pop(0)
            if name in poisoned:
                print(f"# phase {name}: SKIPPED (bring-up quarantine)",
                      file=sys.stderr)
                continue
            key = f"{name}_sweep" if sweep else name
            timeout = PHASE_TIMEOUT_S.get(key, PHASE_TIMEOUT_S.get(name, 900))
            rows, ok, detail = _run_phase(name, sweep, timeout)
            all_rows.extend(rows)
            if not ok and "timed out" in detail:
                wedged = True
                # a phase timeout is the wedge signature: re-probe chip
                # health BEFORE dispatching the next phase, and when the
                # chip is gone, journal the remainder as pending for
                # `obs bringup --resume` instead of running every
                # remaining phase into the wedge (the BENCH_r04/r05
                # fourteen-hour failure mode)
                if no_probe:
                    reprobe = {"healthy": True,
                               "detail": "skipped (--no-probe)"}
                else:
                    reprobe = compile_guard.probe(timeout_s=PROBE_TIMEOUT_S)
                print(f"# post-timeout probe: {reprobe}", file=sys.stderr)
                if not reprobe["healthy"]:
                    pending = [n for n in todo if n not in poisoned]
                    print(f"# chip unhealthy — {len(pending)} phase(s) "
                          f"recorded pending: {pending}", file=sys.stderr)
                    if bringup is not None and pending:
                        try:
                            bringup.record_phases_pending(pending, reprobe)
                        except Exception as e:
                            print(f"# journal write failed: {e!r}",
                                  file=sys.stderr)
                    break
    else:
        wedged = True

    headline = next(
        (r for r in all_rows
         if r.get("phase") == "decode" and (r["bs"], r["ctx"]) == (64, 4096)),
        None,
    )
    from flashinfer_tpu.obs import hwspec

    peak = (headline or {}).get(
        "peak", hwspec.CHIP_SPECS[hwspec.DEFAULT_CHIP].hbm_tbps)
    tbps = (headline or {}).get("tbps", 0.0)
    result = {
        "metric": "batch_decode_attention_bandwidth_bs64_ctx4k",
        "value": round(tbps, 4),
        "unit": "TB/s",
        "vs_baseline": round(tbps / peak, 4),
    }
    sampling = next((r for r in all_rows
                     if r.get("phase") == "sampling" and r["bs"] == 64), None)
    if sampling:
        result["sampling_128k_bs64_us"] = sampling["kernel_us"]
    serving = next((r for r in all_rows
                    if r.get("phase") == "serving" and "tok_s_per_chip" in r),
                   None)
    if serving:
        # BASELINE.md north star: tokens/sec/chip, 70B bs=64 ctx=4k.
        # The 80-layer figure is a two-depth slope extrapolation (one chip,
        # no ICI) — the flag rides along so downstream readers see it.
        result["serving_tok_s_per_chip"] = serving["tok_s_per_chip"]
        result["serving_extrapolated"] = serving.get("extrapolated", False)
    e2e = next((r for r in all_rows if r.get("mode") == "e2e_measured"), None)
    if e2e:
        result["serving_e2e_overhead_vs_slope"] = e2e["overhead_vs_slope"]
    if wedged:
        result["wedged"] = True
    if bank:
        _bank({"result": result, "rows": all_rows, "probe": probe,
               "sweep": sweep})
    print(json.dumps(result))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--bank", action="store_true",
                    help="append full run record to BENCH_BANKED.md")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="internal: run one phase in-process")
    ap.add_argument("--only", action="append",
                    help="orchestrate only these phases")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the chip-health preamble (CPU smoke runs)")
    args = ap.parse_args()
    if args.phase:
        if args.phase == "serving_sharded" \
                and os.environ.get("BENCH_SMALL"):
            # CPU-mesh dryrun: the virtual 8-device host platform must
            # exist BEFORE the backend initializes (jax reads XLA_FLAGS
            # at first device use)
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
        PHASES[args.phase](args.sweep)
        return 0
    return orchestrate(args.sweep, args.bank, phases=args.only,
                       no_probe=args.no_probe)


if __name__ == "__main__":
    sys.exit(main())
