"""CLI: ``python -m flashinfer_tpu.obs <cmd>``.

Commands:

- ``report``: run a small tier-1-sized workload with metrics enabled
  (decorated stateless ops + the decode/prefill plan/run lifecycle,
  CPU-safe under ``JAX_PLATFORMS=cpu``) and print the snapshot —
  ``--format json`` (default) or ``--format prom``; ``--chrome-trace
  PATH`` additionally records an op timeline during the workload and
  writes the merged trace.  ``--no-workload`` skips the built-in
  workload and reports whatever this process already recorded (for use
  from a REPL / atexit hook).
- ``doctor``: device/env/backend health — collect_env, the
  FLASHINFER_TPU_* flag matrix, backend resolution, compile-guard
  quarantine state, tuner cache, registry liveness, lint hygiene
  (the reasonless-suppression count the analyzer would fail on),
  cost-model coverage (``@flashinfer_api`` ops with no roofline
  attribution formula), flight-recorder state (span coverage of the
  serving ops — the L005 rule extended to spans), and the ranked
  top-retrace-causes table.
- ``perf``: the roofline doctor — attribute banked bench rows
  (``--banked BENCH_BANKED.md``) through obs.costmodel/obs.roofline
  and print the per-op efficiency table, bound classification, worst
  offenders, padding-waste and per-serving-phase MFU report that the
  round-5 VERDICT computed by hand.  ``--json`` for the
  schema-stable machine form; exits non-zero on malformed banked
  blocks (the CI smoke gate).
- ``trace``: the flight-recorder export (ISSUE 10) — run a tiny
  ``ServingEngine`` loop (two requests of ``--steps`` tokens, default
  9) with the spans gate + metrics + op timeline ALL on, the request
  lifecycle metered by the engine, and (unless ``--no-perturb``) one
  deliberately perturbed static at the end, then write the UNIFIED
  chrome trace (lifecycle spans + op events + registry snapshot on one
  clock base) to ``--out``.  ``--selftest`` exits non-zero unless the
  export is schema-valid, the loop held the compile-once retrace
  budget (one trace a rung), and the perturbed static was named in the
  retrace-cause table — the CI gate (lint.yml), the perf/3 smoke-gate
  precedent.
- ``steploop``: the step-loop flight deck (obs.steploop) — run the
  same tiny engine loop with the ``FLASHINFER_TPU_STEPLOOP``
  gate on, write the unified trace with the host/device step lanes
  merged in, and print the ledger summary (host_frac, Amdahl ceiling,
  sub-phase decomposition).  ``--selftest`` exits non-zero on a
  missing device lane, any negative gap (clock-base skew), host time
  the named sub-phases did not attribute, or a ledger decomposition
  that does not sum to the measured loop wall within 5% — the CI gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _workload() -> None:
    """A tier-1-sized pass over the instrumented surface: stateless
    decorated ops plus one full plan/run lifecycle per batch wrapper
    family (small shapes; runs in seconds on CPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 256), jnp.float32)
    fi.rmsnorm(x, jnp.ones((256,), jnp.float32))
    fi.silu_and_mul(jax.random.normal(key, (4, 512), jnp.float32))
    probs = jax.nn.softmax(jax.random.normal(key, (2, 64), jnp.float32))
    fi.sampling_from_probs(probs, key)

    T, HQ, HKV, D = 8, 4, 2, 64
    q = jax.random.normal(key, (T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (T, HKV, D),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (T, HKV, D),
                          jnp.bfloat16)
    fi.single_prefill_with_kv_cache(q, k, v, causal=True)

    # decode wrapper lifecycle: plan, re-plan (counted), run
    bs, PS, ppr = 2, 4, 2
    npages = bs * ppr
    kc = jax.random.normal(key, (npages, PS, HKV, D), jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 3),
                           (npages, PS, HKV, D), jnp.bfloat16)
    indptr = np.arange(bs + 1, dtype=np.int32) * ppr
    indices = np.arange(npages, dtype=np.int32)
    last = np.full((bs,), PS, np.int32)
    w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="NHD")
    w.plan(indptr, indices, last, HQ, HKV, D, PS)
    w.plan(indptr, indices, last, HQ, HKV, D, PS)  # re-plan
    qd = jax.random.normal(jax.random.fold_in(key, 4), (bs, HQ, D),
                           jnp.bfloat16)
    w.run(qd, (kc, vc))

    # paged-prefill wrapper lifecycle (the gather path off-TPU)
    wp = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="NHD")
    wp.plan(np.arange(bs + 1, dtype=np.int32) * 2, indptr, indices, last,
            HQ, HKV, D, PS, causal=True)
    qp = jax.random.normal(jax.random.fold_in(key, 5), (bs * 2, HQ, D),
                           jnp.bfloat16)
    wp.run(qp, (kc, vc))


def _serving_workload(steps: int, perturb: bool) -> dict:
    """A tiny ``ServingEngine`` run (tiny Llama, CPU-safe): two requests
    of ``steps`` tokens each, so one mixed step takes both prompts and
    ``steps - 1`` decode steps follow on the decode rung; the engine
    meters each request's lifecycle itself.  With ``perturb``, one more
    request afterwards meets EXACTLY ONE moved static (the dtype of the
    embedding table) on a rung that has compiled, so the retrace-cause
    attribution has a known answer.  Returns the selftest facts."""
    import time

    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import obs
    from flashinfer_tpu.models import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(
        num_pages=32, page_size=8, max_batch=2, prefill_budget_tokens=8,
        max_seq_tokens=32,
        sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95)))
    prompts = ([3, 1, 4], [1, 5, 9, 2, 6])
    for b, prompt in enumerate(prompts):
        eng.submit(EngineRequest(f"req{b}", list(prompt),
                                 max_new_tokens=int(steps)))
    with obs.span("serving.loop", cat="request"):
        loop_t0 = time.perf_counter()
        eng.run()
        loop_wall_s = time.perf_counter() - loop_t0
    traces_loop, rungs = eng.num_traces, len(eng._rung_traced)

    cause_keys = []
    if perturb:
        # the deliberate perturbation: ONE static moves (the embedding
        # table f32 -> bf16) under a rung that has compiled; the
        # attribution must name exactly that leaf
        eng.params = dict(eng.params,
                          embed=eng.params["embed"].astype(jnp.bfloat16))
        eng.submit(EngineRequest("perturbed", list(prompts[1]),
                                 max_new_tokens=1))
        eng.run()
        from flashinfer_tpu.obs import spans as _spans

        cause_keys = [r["key"] for r in
                      _spans.top_retrace_causes(obs.snapshot())
                      if r["wrapper"] == "ServingEngine"]
    return {
        "num_traces_loop": traces_loop,
        "rungs": rungs,
        "steps": int(steps),
        "loop_wall_s": loop_wall_s,
        "cause_keys": cause_keys,
    }


def _engine_workload(num_requests: int,
                     backend: str = "reference") -> dict:
    """A short Zipf-skewed continuous-batching run through the serving
    engine (tiny Llama, CPU-safe) with the request lifecycle metered —
    the ``obs trace --engine`` selftest workload.  Returns the facts
    the selftest gates on: total traces vs the 9-step retrace budget,
    the measured prefix-cache hit rate (must be non-zero under a Zipf
    prompt mix, or the trie is dead), and the served tokens — the
    selftest replays the SAME seeded workload on both attention
    backends and fails on any token divergence (the kernel tier's
    parity gate)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu import obs
    from flashinfer_tpu.models import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    snap0 = obs.snapshot()

    def _hits(snap):
        return (sum(snap["counters"].get(
                    "engine.prefix_hit_tokens", {}).values()),
                sum(snap["counters"].get(
                    "engine.prefix_miss_tokens", {}).values()))

    h0, m0 = _hits(snap0)
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(
        num_pages=96, page_size=8, max_batch=4,
        prefill_budget_tokens=24, max_seq_tokens=64,
        sampling=SamplingConfig(temperature=0.8, top_k=20),
        attention_backend=backend))
    rng = np.random.default_rng(0)
    prefixes = [[int(t) for t in rng.integers(1, cfg.vocab_size, 17)]
                for _ in range(4)]
    zipf = np.minimum(rng.zipf(1.5, num_requests) - 1, len(prefixes) - 1)
    with obs.span("engine.workload", cat="request",
                  backend=backend):
        for i in range(num_requests):
            prompt = prefixes[int(zipf[i])] + [
                int(t) for t in rng.integers(1, cfg.vocab_size, 4)]
            eng.submit(EngineRequest(f"req{i}", prompt,
                                     max_new_tokens=3))
        results = eng.run()
    h1, m1 = _hits(obs.snapshot())
    hits, misses = h1 - h0, m1 - m0
    return {
        "num_traces": eng.num_traces,
        "rungs": len(eng._rung_traced),
        "requests": num_requests,
        "prefix_hit_rate": hits / max(hits + misses, 1),
        "flops_avoided": eng.flops_avoided,
        "results": results,
    }


def _engine_spill_workload(spill: bool) -> dict:
    """The ``obs trace --engine --spill`` workload: low-priority
    requests mid-decode are preempted by later high-priority arrivals
    under a pool sized to force it.  ``spill=True`` runs the tiered
    engine (kv_offload=host, spill_policy=spill — every resume must
    RESTORE); ``spill=False`` runs the never-preempted oracle (big
    pool, no tier) over the SAME seeded requests, so the selftest can
    pin token equality.  Returns the tier facts the gates read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.models import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    tier = (dict(num_pages=9, kv_offload="host", spill_policy="spill",
                 host_gib=1) if spill else dict(num_pages=64))
    eng = ServingEngine(cfg, params, EngineConfig(
        page_size=8, max_batch=2, prefill_budget_tokens=16,
        max_seq_tokens=48,
        sampling=SamplingConfig(temperature=0.8, top_k=20), **tier))
    rng = np.random.default_rng(3)
    lo = [[int(t) for t in rng.integers(1, cfg.vocab_size, 20)]
          for _ in range(2)]
    hi = [[int(t) for t in rng.integers(1, cfg.vocab_size, 20)]
          for _ in range(2)]
    for i, p in enumerate(lo):
        eng.submit(EngineRequest(f"lo{i}", p, max_new_tokens=6,
                                 priority=5))
    for _ in range(5):
        eng.step()  # low-priority lanes are mid-decode
    for i, p in enumerate(hi):
        eng.submit(EngineRequest(f"hi{i}", p, max_new_tokens=4,
                                 priority=0))
    results = eng.run()
    return {
        "num_traces": eng.num_traces,
        "rungs": len(eng._rung_traced),
        "results": results,
        **{k: eng.kv_tier_stats[k]
           for k in ("spills", "restores", "recomputes")},
    }


def cmd_trace(args) -> int:
    os.environ["FLASHINFER_TPU_SPANS"] = "1"
    os.environ["FLASHINFER_TPU_METRICS"] = "1"
    from flashinfer_tpu import obs, profiler
    from flashinfer_tpu.obs import export, spans

    profiler.start_timeline()
    kfacts = None
    if args.engine and args.spill:
        facts = _engine_spill_workload(spill=True)
        kfacts = None
        oracle = _engine_spill_workload(spill=False)
    elif args.engine:
        facts = _engine_workload(args.requests)
        # the kernel attention tier over the SAME seeded workload: the
        # selftest gates BOTH backends on the retrace budget and pins
        # cross-tier token parity (docs/serving.md backend matrix)
        kfacts = _engine_workload(args.requests, backend="kernel")
    else:
        facts = _serving_workload(args.steps, perturb=not args.no_perturb)
    events = profiler.stop_timeline()
    snap = obs.snapshot()
    trace = export.write_unified_trace(args.out, snap, events,
                                       spans.drain())
    problems = export.validate_chrome_trace(trace,
                                            require_lifecycle=True)
    if args.engine and args.spill:
        # the TIERED-KV gates (docs/serving.md §"Tiered KV &
        # disaggregation"): forced preemption under spill_policy=spill
        # must actually SPILL (a zero count means the tier silently
        # fell back), every resume must RESTORE (zero recomputes),
        # restored tokens must equal the never-preempted oracle's
        # bitwise, and the rung ladder must hold
        if facts["spills"] <= 0:
            problems.append(
                "silent spill: preemption was forced under "
                "spill_policy=spill but zero page runs reached the "
                "host tier")
        if facts["restores"] <= 0:
            problems.append(
                "spilled requests resumed without a restore — the "
                "staged-entry admission path is dead")
        if facts["recomputes"] > 0:
            problems.append(
                f"{facts['recomputes']} resume(s) RECOMPUTED under "
                "spill_policy=spill — the host tier lost entries it "
                "had capacity for")
        if facts["results"] != oracle["results"]:
            bad = [rid for rid in oracle["results"]
                   if facts["results"].get(rid) != oracle["results"][rid]]
            problems.append(
                f"spill-restore token mismatch on {len(bad)} "
                f"request(s) (first: {bad[:3]}) vs the never-preempted "
                "oracle — the restore path is not bit-exact")
        if facts["num_traces"] > 9:
            problems.append(
                f"spill-mode retrace budget: {facts['num_traces']} "
                "traces (budget: 9)")
        if facts["num_traces"] > facts["rungs"]:
            problems.append(
                f"spill mode retraced: {facts['num_traces']} traces "
                f"for {facts['rungs']} rungs (compile-once broke)")
    elif args.engine:
        # the ENGINE retrace budget: the whole Zipf run must stay on
        # the pre-compiled rung ladder (<= 9 traces, the same budget
        # the two-request loop pins), and the prefix cache must be LIVE
        # (a zero hit rate under a Zipf prompt mix means the trie or
        # the block-sharing path silently broke)
        if facts["num_traces"] > 9:
            problems.append(
                f"engine retrace budget: {facts['num_traces']} traces "
                f"across {facts['requests']} requests (budget: 9)")
        if facts["num_traces"] > facts["rungs"]:
            problems.append(
                f"engine retraced: {facts['num_traces']} traces for "
                f"{facts['rungs']} rungs (compile-once broke)")
        if facts["prefix_hit_rate"] <= 0.0:
            problems.append(
                "prefix-cache hit rate is ZERO under a Zipf-shared "
                "prompt mix — the prefix trie is not taking hits")
        # the kernel tier: same budget, plus token parity vs the
        # reference tier (everything is seeded, so agreement is exact)
        if kfacts["num_traces"] > 9:
            problems.append(
                f"kernel-tier retrace budget: {kfacts['num_traces']} "
                f"traces across {kfacts['requests']} requests "
                "(budget: 9)")
        if kfacts["num_traces"] > kfacts["rungs"]:
            problems.append(
                f"kernel tier retraced: {kfacts['num_traces']} traces "
                f"for {kfacts['rungs']} rungs (compile-once broke)")
        if kfacts["results"] != facts["results"]:
            bad = [rid for rid in facts["results"]
                   if kfacts["results"].get(rid) != facts["results"][rid]]
            problems.append(
                f"kernel-vs-reference token mismatch on {len(bad)} "
                f"request(s) (first: {bad[:3]}) — the work-unit "
                "lowering diverged from the oracle tier")
    else:
        # the compile-once retrace budget over the serving loop: one
        # trace a rung, CI-gated with attribution
        if facts["num_traces_loop"] > facts["rungs"]:
            problems.append(
                f"retrace budget: {facts['num_traces_loop']} traces "
                f"across {facts['steps']} steps on {facts['rungs']} "
                "rung(s) (budget: one a rung)")
        want = ["params['embed']"]
        if not args.no_perturb and facts["cause_keys"] != want:
            problems.append(
                "deliberate embedding-dtype perturb attributed to "
                f"{facts['cause_keys']!r}, expected {want!r}")

    ls = obs.lifecycle_snapshot()

    def pcts(name):
        h = ls.get(name)
        if not h:
            return "n/a"
        return (f"p50={h.get('p50', 0):.0f} p99={h.get('p99', 0):.0f} "
                f"(n={h['count']})")

    print(f"# unified trace -> {args.out} "
          f"({len(trace['traceEvents'])} events)", file=sys.stderr)
    print(f"# lifecycle: ttft_us {pcts('lifecycle.ttft_us')} | "
          f"tpot_us {pcts('lifecycle.tpot_us')} | "
          f"queue_us {pcts('lifecycle.queue_us')}", file=sys.stderr)
    causes = spans.top_retrace_causes(snap)
    if causes:
        print("# top retrace causes:", file=sys.stderr)
        for r in causes:
            print(f"#   {r['count']:4d}  {r['wrapper']}.{r['key']}",
                  file=sys.stderr)
    summary = {
        "out": args.out,
        "events": len(trace["traceEvents"]),
        "retrace_causes": causes,
        "problems": problems,
        **{k: v for k, v in facts.items()
           if k not in ("requests", "results")},
    }
    if kfacts is not None:
        summary["kernel_backend"] = {
            k: v for k, v in kfacts.items()
            if k not in ("requests", "results")}
        summary["kernel_backend"]["tokens_equal"] = \
            kfacts["results"] == facts["results"]
    print(json.dumps(summary, indent=1, sort_keys=True))
    if problems and args.selftest:
        for p in problems:
            print(f"error: {p}", file=sys.stderr)
        return 2
    return 0


def cmd_steploop(args) -> int:
    """Step-loop flight deck selftest (ISSUE 17): drive the tiny engine
    loop with the steploop gate ON, merge the host/device
    step lanes into the unified trace, and gate on the ledger's internal
    consistency — every step must carry a device window (the completion
    probe ran), no gap may be negative (both edges share one
    perf_counter base, so a negative gap means the clock math broke),
    the named sub-phases must attribute the host window, and the
    gap/device decomposition must sum to the EXTERNALLY measured loop
    wall within 5% (dropped records or clock skew cannot hide)."""
    os.environ["FLASHINFER_TPU_STEPLOOP"] = "1"
    os.environ["FLASHINFER_TPU_SPANS"] = "1"
    os.environ["FLASHINFER_TPU_METRICS"] = "1"
    from flashinfer_tpu import obs, profiler
    from flashinfer_tpu.obs import export, spans, steploop

    steploop.reset()
    profiler.start_timeline()
    facts = _serving_workload(args.steps, perturb=False)
    events = profiler.stop_timeline()
    snap = obs.snapshot()
    recs = steploop.ledger().records()
    trace = export.write_unified_trace(
        args.out, snap, events, spans.drain(),
        extra_events=steploop.trace_events(recs))
    problems = export.validate_chrome_trace(trace)
    s = steploop.summarize(recs)

    if s["steps"] < int(args.steps):
        problems.append(
            f"ledger recorded {s['steps']} steps across a "
            f"{args.steps}-step loop — the ServingEngine wiring is dead")
    if s["missing_device_lane"]:
        problems.append(
            f"{s['missing_device_lane']} step(s) missing the device "
            "window — the completion probe did not run")
    if s["negative_gaps"]:
        problems.append(
            f"{s['negative_gaps']} negative gap(s) — dispatch/done "
            "stamps disagree on the clock base")
    if s["unattributed_frac"] is not None \
            and s["unattributed_frac"] > 0.10:
        problems.append(
            f"{s['unattributed_frac']:.1%} of host time unattributed "
            "(> 10%) — a call site skipped a sub-phase mark")
    if not any(ev.get("cat") == "steploop"
               and ev.get("tid") == steploop.TRACE_TID_DEVICE
               for ev in trace["traceEvents"]):
        problems.append("no steploop device lane in the unified trace")
    # the wall check: host(first) + device + gap covers begin(first) ->
    # done(last) by construction, so it must match the externally timed
    # loop wall — a mismatch means records were lost or clocks skewed
    comp_us = 0.0
    for r in recs:
        if r["idle"]:
            continue
        comp_us += r["host_us"] if r["gap_us"] is None \
            else max(r["gap_us"], 0.0)
        comp_us += r["device_us"] or 0.0
    wall = facts["loop_wall_s"]
    if wall > 0 and abs(comp_us / 1e6 - wall) / wall > 0.05:
        problems.append(
            f"ledger decomposition {comp_us / 1e6:.4f}s vs measured "
            f"loop wall {wall:.4f}s — more than 5% apart")

    print(f"# unified trace -> {args.out} "
          f"({len(trace['traceEvents'])} events)", file=sys.stderr)
    summary = {
        "out": args.out,
        "events": len(trace["traceEvents"]),
        "loop_wall_s": wall,
        "decomposed_s": comp_us / 1e6,
        "problems": problems,
        "steploop": s,
    }
    print(json.dumps(summary, indent=1, sort_keys=True))
    if problems and args.selftest:
        for p in problems:
            print(f"error: {p}", file=sys.stderr)
        return 2
    return 0


def cmd_report(args) -> int:
    from flashinfer_tpu import obs, profiler
    from flashinfer_tpu.obs import export

    os.environ["FLASHINFER_TPU_METRICS"] = "1"
    events = None
    if not args.no_workload:
        if args.chrome_trace:
            profiler.start_timeline()
        _workload()
        if args.chrome_trace:
            events = profiler.stop_timeline()
    snap = obs.snapshot()
    if args.chrome_trace:
        export.write_chrome_trace(args.chrome_trace, snap, events)
        print(f"# chrome trace -> {args.chrome_trace}", file=sys.stderr)
    if args.format == "prom":
        sys.stdout.write(export.to_prometheus(snap))
    else:
        print(export.to_json(snap))
    return 0


def cmd_doctor(args) -> int:
    """Health report: environment, devices, backend resolution, caches,
    quarantine — everything a bug report / perf triage needs up front."""
    from flashinfer_tpu.collect_env import collect_env

    report = {"env": collect_env()}

    flags = {}
    for name in ("FLASHINFER_TPU_METRICS", "FLASHINFER_TPU_SPANS",
                 "FLASHINFER_TPU_SPANS_CAP", "FLASHINFER_TPU_STEPLOOP",
                 "FLASHINFER_TPU_STEPLOOP_CAP", "FLASHINFER_TPU_LOGLEVEL",
                 "FLASHINFER_TPU_BACKEND", "FLASHINFER_TPU_INTERPRET",
                 "FLASHINFER_TPU_TIMELINE_SYNC", "FLASHINFER_TPU_TRACE_DUMP",
                 "FLASHINFER_TPU_TRACE_APPLY", "FLASHINFER_TPU_CACHE_DIR",
                 "FLASHINFER_TPU_DUMP_DIR"):
        flags[name] = os.environ.get(name, "<unset>")
    report["flags"] = flags

    try:
        from flashinfer_tpu.utils import is_tpu, resolve_backend

        report["backend_resolution"] = {
            "is_tpu": bool(is_tpu()),
            "single_decode_auto": resolve_backend("auto", "single_decode"),
        }
    except Exception as e:  # device init can fail off-accelerator
        report["backend_resolution"] = f"<unavailable: {type(e).__name__}>"

    from flashinfer_tpu import compile_guard

    q = compile_guard._load_qlist()
    report["quarantine"] = {
        "entries": len(q),
        "ops": sorted({i.get("op", "?") for i in q.values()}),
    }

    # hardware bring-up session state (ISSUE 20): journal length, rung
    # outcomes, wedge quarantine, and which tuning sections still ship
    # seed tactics — the at-a-glance answer to "where did the chip
    # session get to"
    try:
        from flashinfer_tpu.obs import bringup

        report["bringup"] = bringup.doctor_summary()
    except Exception as e:
        report["bringup"] = f"<unavailable: {type(e).__name__}>"
    try:
        from flashinfer_tpu.autotuner import AutoTuner

        t = AutoTuner.get()
        t._load()
        report["tuner"] = {"cache": str(t._cache_path()),
                          "entries": len(t._cache)}
    except Exception as e:
        report["tuner"] = f"<unavailable: {type(e).__name__}>"

    from flashinfer_tpu import obs, profiler

    snap = obs.snapshot()
    report["registry"] = {
        "metrics_enabled": obs.metrics_enabled(),
        "counters": len(snap["counters"]),
        "gauges": len(snap["gauges"]),
        "histograms": len(snap["histograms"]),
        "timeline_active": profiler.timeline_active(),
    }

    # flight recorder (ISSUE 10): gate + ring state, serving-op span
    # coverage (every catalog.SERVING_OPS op must declare its span
    # category in spans.SPAN_CATEGORIES — the L005 ships-observed rule
    # extended to the span layer, so the unspanned list must stay
    # empty), and the ranked top-retrace-causes table from this
    # process's plan.retrace_cause cells
    try:
        from flashinfer_tpu.obs import spans as _spans
        from flashinfer_tpu.obs.catalog import SERVING_OPS

        rec = _spans.get_recorder()
        # delegated to the L013 registry_coverage pass — the ONE
        # implementation of the span-coverage rule (ISSUE 15); same
        # sorted-list output as the pre-delegation inline set
        # difference, byte for byte.  The fallback mirrors the
        # delegated implementation so the spans section stays alive
        # when the ANALYSIS package is the broken part of the tree
        # (importing the pass runs the full package init) — the pass
        # remains the enforcement point.
        try:
            from flashinfer_tpu.analysis import registry_coverage as _rc
            unspanned = _rc.unspanned_serving_ops()
        except Exception:
            unspanned = sorted(SERVING_OPS - set(_spans.SPAN_CATEGORIES))
        report["spans"] = {
            "enabled": obs.spans_enabled(),
            "capacity": rec.capacity,
            "recorded": rec.total,
            "dropped": rec.dropped(),
            "serving_ops": sorted(SERVING_OPS),
            "unspanned_serving_ops": unspanned,
        }
        report["retrace_causes"] = _spans.top_retrace_causes(snap)
    except Exception as e:  # doctor must never crash on a broken tree
        report["spans"] = f"<unavailable: {type(e).__name__}>"

    # static-analysis hygiene: a reasonless `# graft-lint: ok` /
    # `# wedge-lint: ok` is an unreviewable waiver (L000/W000 — the
    # analyzer fails on them, they can never be baselined); a non-zero
    # count here means the tree cannot pass lint
    try:
        import flashinfer_tpu as _fi
        from flashinfer_tpu.analysis import core as _acore

        pkg = os.path.dirname(os.path.abspath(_fi.__file__))
        total = reasonless = 0
        for path in _acore.iter_python_files([pkg]):
            sf = _acore.load_file(path)
            for table in (sf.suppressions, sf.wedge_suppressions):
                total += len(table)
                reasonless += sum(
                    1 for reason in table.values() if not reason)
        report["lint"] = {
            "suppressions": total,
            "reasonless_suppressions": reasonless,
        }

        # L014/L015 kernel coverage: a silently-skipped kernel body is
        # an unanalyzed DMA pipeline — surface analyzed-vs-skipped here
        # so the skip count is visible without reading analyzer output
        # (docs/static_analysis.md §"L014 hazard classes")
        from flashinfer_tpu.analysis import dma_race as _dma
        from flashinfer_tpu.analysis import mosaic_lowering as _mosaic

        proj = _acore.Project.from_paths([pkg])
        d14 = _dma.stats(proj)
        d15 = _mosaic.stats(proj)
        report["lint"]["l014_kernels"] = {
            "analyzed": d14["kernels_analyzed"],
            "skipped": d14["kernels_skipped"],
            "no_dma": d14["kernels_no_dma"],
            "sites_unresolved": d14["sites_unresolved"],
        }
        report["lint"]["l015_kernels"] = {
            "linted": d15["kernels_linted"],
            "sites_unresolved": d15["sites_unresolved"],
            "findings_by_rule": dict(d15["findings_by_rule"]),
        }

        # L016/L017 cost-parity coverage: a skipped family is a cost
        # model nothing checks, an unpriced knob a choice nothing
        # proves — surface checked-vs-skipped and the worst observed
        # deviation so drift headroom is visible at a glance
        from flashinfer_tpu.analysis import chooser_coverage as _chz
        from flashinfer_tpu.analysis import cost_parity as _cpar

        d16 = _cpar.stats(proj)
        report["lint"]["l016_kernels"] = {
            "families_checked": d16["families_checked"],
            "families_skipped": d16["families_skipped"],
            "max_deviation": d16["max_deviation"],
            "skip_reasons": dict(d16["skip_reasons"]),
        }
        d17 = _chz.stats(proj)
        report["lint"]["l017"] = {
            "choosers": d17["choosers"],
            "waivers": d17["waivers"],
            "bindings": d17["bindings"],
            "findings": d17["findings"],
        }
    except Exception as e:  # doctor must never crash on a broken tree
        report["lint"] = f"<unavailable: {type(e).__name__}>"

    # continuous-batching engine (serve/engine.py): pool occupancy,
    # prefix-cache hit rate, eviction/preemption pressure — read from
    # this process's registry cells (zeros in a fresh process; the
    # serving process's doctor shows the live numbers)
    try:
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})

        def cell(name):
            return sum(counters.get(name, {}).values())

        def gauge(name):
            cells = gauges.get(name, {})
            return cells.get("") if cells else None

        hits = cell("engine.prefix_hit_tokens")
        misses = cell("engine.prefix_miss_tokens")
        report["engine"] = {
            "requests": cell("engine.requests"),
            "finished": cell("engine.finished"),
            "steps": cell("engine.steps"),
            "prefix_hit_tokens": hits,
            "prefix_miss_tokens": misses,
            "prefix_hit_rate": (hits / (hits + misses)
                                if hits + misses else None),
            "evictions": cell("engine.evictions"),
            "preemptions": cell("engine.preemptions"),
            "pool_pages_in_use": gauge("engine.pool_pages_in_use"),
            "pool_pages_free": gauge("engine.pool_pages_free"),
        }
        # tiered KV (serve/kv_tier.py): per-tier occupancy + the
        # spill/restore/migration traffic and resume-miss attribution
        # — zeros in a fresh process, live numbers in the serving one
        restores = cell("engine.kv_tier.restores")
        recomputes = cell("engine.kv_tier.recomputes")
        report["kv_tier"] = {
            "spills": cell("engine.kv_tier.spills"),
            "spill_bytes": cell("engine.kv_tier.spill_bytes"),
            "restores": restores,
            "restore_bytes": cell("engine.kv_tier.restore_bytes"),
            "migrations": cell("engine.kv_tier.migrations"),
            "migrate_bytes": cell("engine.kv_tier.migrate_bytes"),
            "recomputes": recomputes,
            "restore_rate": (restores / (restores + recomputes)
                             if restores + recomputes else None),
            "host_evictions": cell("engine.kv_tier.host_evictions"),
            "host_pages": gauge("engine.kv_tier.host_pages"),
            "host_bytes": gauge("engine.kv_tier.host_bytes"),
        }
    except Exception as e:  # doctor must never crash on a broken tree
        report["engine"] = f"<unavailable: {type(e).__name__}>"
        report["kv_tier"] = f"<unavailable: {type(e).__name__}>"

    # step-loop flight deck (obs.steploop): gate state plus the live
    # ledger summary — looked up via sys.modules, never imported, so
    # doctor itself cannot defeat the zero-overhead default (the same
    # rule roofline's live join follows); zeros/absent in a fresh
    # process, live host_frac / worst sub-phase in the serving one
    try:
        report["host_loop"] = {"enabled": obs.steploop_enabled()}
        _sl = sys.modules.get("flashinfer_tpu.obs.steploop")
        if _sl is not None:
            s = _sl.summarize()
            report["host_loop"].update(
                steps=s["steps"], idle_ticks=s["idle_ticks"],
                dropped=s["dropped"], surfaces=s["surfaces"],
                host_frac=s["host_frac"],
                overlap_efficiency=s["overlap_efficiency"],
                amdahl_ceiling=s["amdahl_ceiling"],
                worst_phase=s["worst_phase"],
                phases_us=s["phases"],
                unattributed_frac=s["unattributed_frac"],
                negative_gaps=s["negative_gaps"],
                missing_device_lane=s["missing_device_lane"],
                gc=s["gc"], compile_log_lines=s["compile_log_lines"])
    except Exception as e:  # doctor must never crash on a broken tree
        report["host_loop"] = f"<unavailable: {type(e).__name__}>"

    # cost-model coverage (mirrors analysis L005's obs-coverage idea):
    # a decorated public op with no obs.costmodel family can bench but
    # never roofline-attribute — new ops must not silently ship
    # unattributed, so the uncovered list must stay empty
    try:
        from flashinfer_tpu.obs import costmodel, hwspec

        report["costmodel"] = {
            "api_ops_covered": len(costmodel.API_OP_COSTS),
            "uncovered_api_ops": list(costmodel.uncovered_api_ops()),
            "chip": hwspec.detect_chip(),
        }
    except Exception as e:
        report["costmodel"] = f"<unavailable: {type(e).__name__}>"
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


def cmd_bringup(args) -> int:
    """Hardware graduation session harness (ISSUE 20) — flags are owned
    by :mod:`flashinfer_tpu.obs.bringup` (``--selftest``, ``--resume``,
    ``--graduate``, ``--list``, ...)."""
    from flashinfer_tpu.obs import bringup

    return bringup.main(list(args.rest))


def cmd_perf(args) -> int:
    """Roofline doctor over banked bench rows — the VERDICT analysis,
    reproduced mechanically (no jax / no device needed)."""
    from flashinfer_tpu.obs import bench_audit, roofline

    path = args.banked
    if path is None:
        # a history kept at the root of the checkout; where none was
        # asked for and none is kept, the report still carries every
        # section computed from shapes alone
        kept = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "BENCH_BANKED.md")
        path = kept if os.path.exists(kept) else None
    rows = []
    if path is not None:
        try:
            rows = bench_audit.load_banked_history(path, strict=True)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if not rows:
            print(f"error: no bench rows found in {path}", file=sys.stderr)
            return 2
    report = roofline.build_perf_report(rows, chip=args.chip)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        sys.stdout.write(roofline.render_perf_report(report))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "bringup":
        # delegated wholesale: bringup owns its flags (--selftest,
        # --resume, --graduate, ...) and argparse REMAINDER cannot
        # forward leading options through a subparser
        from flashinfer_tpu.obs import bringup

        return bringup.main(argv[1:])
    p = argparse.ArgumentParser(prog="python -m flashinfer_tpu.obs")
    sub = p.add_subparsers(dest="cmd", required=True)
    sp = sub.add_parser("report", help="metrics snapshot (runs a small "
                                       "built-in workload by default)")
    sp.add_argument("--format", choices=["json", "prom"], default="json")
    sp.add_argument("--no-workload", action="store_true",
                    help="report this process's registry as-is")
    sp.add_argument("--chrome-trace", metavar="PATH", default=None,
                    help="also write the merged op-timeline chrome trace")
    sp.set_defaults(fn=cmd_report)
    sp = sub.add_parser("doctor", help="device/env/backend health report")
    sp.set_defaults(fn=cmd_doctor)
    sp = sub.add_parser("perf", help="roofline attribution report over "
                                     "banked bench rows")
    sp.add_argument("--banked", metavar="PATH", default=None,
                    help="BENCH_BANKED.md-style history "
                         "(default: the repo's BENCH_BANKED.md)")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable report (schema "
                         "flashinfer_tpu.obs.perf/3: + serving_ici / "
                         "scaling_prediction ICI fields + the "
                         "serving_disagg kv_migrate join)")
    sp.add_argument("--chip", default=None,
                    help="default chip for rows that name none "
                         "(default: v5e, the banked history's chip)")
    sp.set_defaults(fn=cmd_perf)
    sp = sub.add_parser("trace", help="flight-recorder export: unified "
                                      "chrome trace of a metered "
                                      "serving loop")
    sp.add_argument("--out", metavar="PATH",
                    default="/tmp/flashinfer_tpu_unified_trace.json",
                    help="unified chrome-trace output path")
    sp.add_argument("--steps", type=int, default=9,
                    help="tokens per request, one engine step each "
                         "(retrace budget: one trace a rung)")
    sp.add_argument("--no-perturb", action="store_true",
                    help="skip the deliberate one-static perturbation "
                         "(and its attribution assert)")
    sp.add_argument("--engine", action="store_true",
                    help="run the continuous-batching ENGINE workload "
                         "instead of the two-request loop: a short "
                         "Zipf-shared-prefix request mix through "
                         "serve/engine.py; --selftest then fails on a "
                         "retrace-budget breach (> 9 traces or any "
                         "trace beyond the rung ladder) or a ZERO "
                         "prefix-cache hit rate")
    sp.add_argument("--requests", type=int, default=24,
                    help="engine-mode request count (Zipf-skewed "
                         "shared prefixes)")
    sp.add_argument("--spill", action="store_true",
                    help="with --engine: run the TIERED-KV workload "
                         "instead — forced preemption under "
                         "spill_policy=spill; --selftest then fails "
                         "on token divergence vs the never-preempted "
                         "oracle, a silent spill (zero spills/"
                         "restores), any recompute fallback, or a "
                         "retrace breach")
    sp.add_argument("--selftest", action="store_true",
                    help="exit non-zero unless the export is "
                         "schema-valid, the retrace budget held, and "
                         "the perturbed static was named (the CI gate)")
    sp.set_defaults(fn=cmd_trace)
    sp = sub.add_parser("steploop",
                        help="step-loop flight deck: host/device "
                             "overlap ledger over a tiny engine "
                             "loop (gate forced ON for the run)")
    sp.add_argument("--out", metavar="PATH",
                    default="/tmp/flashinfer_tpu_steploop_trace.json",
                    help="unified chrome-trace output path (host/"
                         "device step lanes merged in)")
    sp.add_argument("--steps", type=int, default=9,
                    help="engine steps to ledger")
    sp.add_argument("--selftest", action="store_true",
                    help="exit non-zero on a missing device lane, a "
                         "negative gap, unattributed host time, or a "
                         "decomposition that misses the measured loop "
                         "wall by > 5% (the CI gate)")
    sp.set_defaults(fn=cmd_steploop)
    sp = sub.add_parser(
        "bringup",
        help="hardware graduation session: mosaic-risk smoke ladder, "
             "journaled and resumable, and provenance graduation of "
             "emit-config files (ISSUE 20); flags "
             "are owned by obs.bringup (--selftest, --resume, "
             "--graduate, --list, ...)",
        add_help=False)
    sp.set_defaults(fn=cmd_bringup, rest=[])
    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
