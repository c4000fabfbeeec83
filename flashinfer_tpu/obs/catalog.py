"""The authoritative metric catalog.

Single source of truth for every metric the instrumentation layer may
emit: name, type, label schema, and what it means.  Three consumers:

- ``docs/observability.md`` documents from this table (kept in sync by
  hand; the doc test asserts the doc names every catalog entry);
- the L005 analysis pass (``analysis/obs_coverage.py``) fails CI when a
  ``@flashinfer_api``-decorated public op is missing from ``API_OPS`` —
  new public ops cannot ship unobserved;
- ``obs report`` / the exporters annotate output with ``help`` strings.

``API_OPS`` lists the op names of the decorated public surface (the
decorator's ``name or f.__qualname__``).  Adding a decorated function
means adding its name here (and to the doc) — that is the point.
"""

from __future__ import annotations

from typing import Dict, Tuple

from flashinfer_tpu.obs.registry import PERCENT_BUCKETS

# (type, labels, help)
METRICS: Dict[str, Tuple[str, Tuple[str, ...], str]] = {
    # -- @flashinfer_api decorator (api_logging.py) -----------------------
    "api.calls": (
        "counter", ("op",),
        "calls through each decorated public op (metrics gate on)"),
    "api.calls_total": (
        "counter", (),
        "instrumented-path call index across all ops — the registry-"
        "backed successor of api_logging's ad-hoc _call_counter; also "
        "the [N] index in FLASHINFER_TPU_LOGLEVEL output"),
    "api.dispatch_us": (
        "histogram", ("op",),
        "host dispatch time per call: wrapper entry to op return, no "
        "device sync (the dispatch-cost number VERDICT weak #4 wanted)"),
    # -- plan/run wrapper lifecycle (decode.py / prefill.py / attention.py)
    "plan.calls": (
        "counter", ("wrapper",),
        "plan() invocations per wrapper class"),
    "plan.replans": (
        "counter", ("wrapper",),
        "plan() calls that replaced a live plan (re-plan churn — each "
        "one risks a recompile if the geometry bucket moved)"),
    "plan.sm_scale_rebinds": (
        "counter", ("wrapper",),
        "frozen-plan sm_scale replacements (per-call k_scale/sm_scale "
        "overrides swapping a dataclasses.replace'd plan in and out)"),
    "plan.soft_cap_rebinds": (
        "counter", ("wrapper",),
        "frozen-plan logits_soft_cap replacements (BatchAttention.run "
        "honoring a per-run cap that differs from the planned one — "
        "the reference-parity rebind; each novel cap value compiles a "
        "fresh kernel variant, so a hot counter here means the caller "
        "should re-plan instead)"),
    "plan.padding_waste_pct": (
        "histogram", ("wrapper", "axis"),
        "planned-vs-actual padding waste per plan(): 100*(1 - "
        "actual/padded) for each padded axis (q/kv token axes, decode "
        "batch and page-table slots; the fused-prefill work-unit axes "
        "prefill_unit_rows / prefill_mxu_cells measure idle tile rows "
        "and idle MXU cells across the planned units — the number the "
        "ISSUE 3 tile packing exists to shrink)"),
    "plan.prefill_units_pruned": (
        "counter", ("wrapper",),
        "fused-prefill work units removed at plan time (provably "
        "all-masked: causal chunks above the diagonal, sliding-window "
        "chunks below the window, all-zero custom-mask windows) — MXU "
        "work the pipelined kernel never sees"),
    "plan.decode_splits": (
        "counter", ("wrapper", "splits"),
        "decode plan() split-KV selections by chosen partition factor "
        "(cost-model-guided, L009-feasibility-pruned; splits=1 means "
        "the unsplit kernel was predicted faster — a hot >1 label "
        "means the short-context split path is live)"),
    # -- compile-once serving step ----------------------------------------
    "serve.step_retraces": (
        "counter", ("wrapper",),
        "serving-step traces beyond the first of a rung or a live plan "
        "(ServingEngine / ShardedServingStep) — the compile-once contract "
        "says this stays at ZERO: a non-zero count means the donated "
        "state's pytree structure, a shape, or a dtype moved between "
        "steps and every step is paying a retrace"),
    "plan.retrace_cause": (
        "counter", ("wrapper", "key"),
        "retrace-cause attribution (FLASHINFER_TPU_SPANS gate): one "
        "count per frozen static that changed when a serving step "
        "retraced under a live plan or a wrapper replan moved its "
        "statics — key names the exact static (the L003 staticness "
        "contract makes the diff well-defined); the ranked table in "
        "`obs doctor` reads these cells"),
    # -- request lifecycle (obs.spans; FLASHINFER_TPU_SPANS gate) ---------
    "lifecycle.queue_us": (
        "histogram", (),
        "request queue wait: enqueue to first work (first prefill "
        "chunk, or first token for decode-only requests)"),
    "lifecycle.ttft_us": (
        "histogram", (),
        "time to first token: enqueue to the first generated token "
        "(explicit TTFT_BUCKETS_US boundaries, 1 ms - 60 s)"),
    "lifecycle.tpot_us": (
        "histogram", (),
        "time per output token: inter-token gap per decode step after "
        "the first (explicit TPOT_BUCKETS_US boundaries, 100 us - 1 s)"),
    "lifecycle.tokens_per_s": (
        "histogram", (),
        "per-request generation rate at finish: generated tokens / "
        "(finish - enqueue)"),
    # -- continuous-batching engine (serve/engine.py) ---------------------
    "engine.requests": (
        "counter", (),
        "requests submitted to the serving engine"),
    "engine.finished": (
        "counter", (),
        "requests completed (max_new_tokens generated)"),
    "engine.steps": (
        "counter", (),
        "engine steps executed (one compiled rung dispatch each)"),
    "engine.step_tokens": (
        "counter", (),
        "scheduled tokens across all engine steps (decode lanes + "
        "prefill chunk tokens; padding excluded)"),
    "engine.prefix_hit_tokens": (
        "counter", (),
        "prompt tokens whose prefill was SKIPPED via a prefix-cache "
        "hit (full-page trie matches adopted at admission) — the "
        "numerator of the prefix hit rate; each hit's avoided FLOPs "
        "are priced by costmodel.engine_step into "
        "ServingEngine.flops_avoided"),
    "engine.prefix_miss_tokens": (
        "counter", (),
        "prompt tokens that had to prefill (no cached block) — the "
        "hit-rate denominator's other half"),
    "engine.evictions": (
        "counter", (),
        "prefix-cache pages LRU-evicted from the block pool (cache-"
        "only pages reclaimed to admit new requests)"),
    "engine.preemptions": (
        "counter", (),
        "running requests preempted-by-eviction (pages released, "
        "recompute-on-resume) so a higher-priority request could "
        "admit"),
    "engine.pool_pages_in_use": (
        "gauge", (),
        "block-pool pages with a non-zero refcount after the latest "
        "engine step (requests + prefix-cache ownership)"),
    "engine.pool_pages_free": (
        "gauge", (),
        "block-pool free-list depth after the latest engine step"),
    "engine.idle_steps": (
        "counter", (),
        "engine.step calls that returned on the empty-schedule early "
        "path (nothing runnable this tick: no dispatch, no device "
        "work) — previously a silent return; counted so host-gap math "
        "and step accounting never mis-attribute idle polls as device "
        "time (the steploop ledger records the same tick as idle)"),
    # -- step-loop flight deck (obs.steploop; FLASHINFER_TPU_STEPLOOP) ----
    "steploop.steps": (
        "counter", ("surface",),
        "serving-step dispatches recorded by the step-loop ledger, per "
        "step surface (ServingEngine / ShardedServingStep)"),
    "steploop.idle_ticks": (
        "counter", ("surface",),
        "idle ticks recorded by the step-loop ledger (empty-schedule "
        "engine polls — no dispatch, no device lane)"),
    "steploop.host_us": (
        "histogram", ("surface",),
        "per-step host window: step entry to async-dispatch return "
        "(the sum of the named sub-phases)"),
    "steploop.phase_us": (
        "histogram", ("surface", "phase"),
        "named sub-phase durations per step (engine host window: admit "
        "/ schedule / assemble / lower / dispatch, their children "
        "lower.build / lower.upload / dispatch.upload / dispatch.call "
        "and theirs: lower.build.l0 / .l1 / .decode, "
        "lower.build.window.l1 / .decode, dispatch.upload.pack / .put / "
        ".cut; then fetch / post; gc and compile.trace / .lower / "
        ".backend / .cache_load inside the step; fused step wrappers: "
        "signature / dispatch) "
        "— the host-gap decomposition ROADMAP item 4's pipeline "
        "refactor is judged against"),
    "steploop.device_us": (
        "histogram", ("surface",),
        "per-step device execution window: async-dispatch return to "
        "completion-probe return (the gate-ON path adds the probe — a "
        "per-step device sync this measurement mode pays)"),
    "steploop.gap_us": (
        "histogram", ("surface",),
        "device idle between step N completion and step N+1 dispatch "
        "per (surface, thread) lane — the host gap; host_frac = "
        "gap / (gap + device), Amdahl ceiling = 1/(1-host_frac)"),
    # -- tiered KV: host offload + disaggregated handoff (serve/kv_tier.py)
    "engine.kv_tier.spills": (
        "counter", (),
        "requests whose KV page runs were offloaded to the host-RAM "
        "tier (preemption under spill_policy spill/auto, or an "
        "explicit offload_idle) — the restore path resumes them "
        "bit-exactly"),
    "engine.kv_tier.spill_bytes": (
        "counter", (),
        "KV bytes moved device -> host across all spills (pages at "
        "the cache's storage dtype — int8/fp8 caches spill at 1 "
        "byte/element, the compressed host format)"),
    "engine.kv_tier.restores": (
        "counter", (),
        "staged KV entries restored into fresh device pages at "
        "admission (host-tier spills AND in-flight kv_migrate "
        "handoffs — both ride the same restore path)"),
    "engine.kv_tier.restore_bytes": (
        "counter", (),
        "KV bytes moved host -> device across all restores"),
    "engine.kv_tier.migrations": (
        "counter", (),
        "prefill-pool -> decode-pool KV handoffs (kv_migrate; the "
        "disaggregated serving collective, ICI-priced by "
        "costmodel.kv_migrate)"),
    "engine.kv_tier.migrate_bytes": (
        "counter", (),
        "KV payload bytes handed prefill -> decode across all "
        "migrations (== the predicted ICI wire bytes at hops=1)"),
    "engine.kv_tier.recomputes": (
        "counter", (),
        "preempted/offloaded requests resumed by RECOMPUTE instead of "
        "restore (spill disabled, policy chose recompute, or the host "
        "store LRU-evicted the entry) — the tier's miss attribution; "
        "a spill-policy bench asserts this stays ZERO when the host "
        "tier absorbed every resume"),
    "engine.kv_tier.host_evictions": (
        "counter", (),
        "host-store entries LRU-evicted under capacity pressure (each "
        "one downgrades that request's resume to recompute — never "
        "silent)"),
    "engine.kv_tier.host_pages": (
        "gauge", (),
        "KV pages currently resident in the host-RAM tier"),
    "engine.kv_tier.host_bytes": (
        "gauge", (),
        "bytes currently resident in the host-RAM tier (capacity is "
        "EngineConfig.host_gib — the engine.host_gib knob)"),
    # -- trace.py solution substitution -----------------------------------
    "trace.solution_hits": (
        "counter", ("op",),
        "TRACE_APPLY calls routed to a registered substitute solution"),
    "trace.solution_misses": (
        "counter", ("op",),
        "TRACE_APPLY calls with no matching solution (fell through to "
        "the default implementation)"),
    # -- fused MoE expert parallelism -------------------------------------
    "moe.dropped_tokens": (
        "counter", ("dispatch",),
        "capacity-dropped (token, choice) routes observed at EAGER "
        "fused_moe_ep calls (inside jit the count is a tracer and is "
        "skipped — use return_dropped=True there)"),
    "moe.ep_a2a_bytes": (
        "counter", ("dispatch",),
        "EP all_to_all payload bytes per TRACED fused_moe_ep call "
        "(dispatch + combine buffers; shapes are static, so this is "
        "the per-call traffic of the compiled program — for "
        "alltoall_exact it is the per-ROUND payload, rounds being "
        "data-dependent).  Joins against the predicted ICI bytes of "
        "costmodel.ep_all_to_all"),
    # -- comm collectives --------------------------------------------------
    "comm.allreduce_bytes": (
        "counter", ("axis",),
        "allreduce payload bytes per TRACED comm.allreduce/"
        "allreduce_fusion call (static shapes: the per-call traffic of "
        "the compiled program; wire bytes = 2(p-1)/p x payload, "
        "costmodel.collective).  Joins measured collective traffic "
        "against the roofline's predicted ICI bytes"),
    # -- bench row quality audit (obs.bench_audit) ------------------------
    "bench.rows": (
        "counter", ("phase", "quality"),
        "bench rows emitted per phase, by audited quality stamp "
        "(ok | degraded | poison)"),
    # -- hardware bring-up observatory (obs.bringup) ----------------------
    "bringup.rungs": (
        "counter", ("outcome",),
        "smoke-ladder rungs executed by `obs bringup`, by outcome "
        "(pass | fail | wedge) — a wedge means the rung was "
        "quarantined and the session halted for --resume"),
}

# histograms whose values are percentages, not microseconds
PERCENT_HISTOGRAMS = ("plan.padding_waste_pct",)

# Explicit request-lifecycle bucket boundaries (ISSUE 10 satellite):
# TTFT spans interactive-serving first-token latencies (1 ms) out to
# the multi-second cold-compile outliers; TPOT spans per-token decode
# cadences (100 us) up to a pathological 1 s/token.  Log-spaced like
# DEFAULT_BUCKETS_US so interpolated p50/p99 stay tight at the scales
# serving SLOs quote.
TTFT_BUCKETS_US: Tuple[float, ...] = (
    1e3, 2e3, 5e3, 1e4, 2e4, 5e4, 1e5, 2e5, 5e5,
    1e6, 2e6, 5e6, 1e7, 2e7, 6e7,
)
TPOT_BUCKETS_US: Tuple[float, ...] = (
    100.0, 200.0, 500.0, 1e3, 2e3, 5e3, 1e4, 2e4, 5e4,
    1e5, 2e5, 5e5, 1e6,
)
TOKENS_PER_S_BUCKETS: Tuple[float, ...] = (
    0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1e3, 2e3, 5e3, 1e4,
)

_LIFECYCLE_BUCKETS = {
    "lifecycle.ttft_us": TTFT_BUCKETS_US,
    "lifecycle.tpot_us": TPOT_BUCKETS_US,
    "lifecycle.tokens_per_s": TOKENS_PER_S_BUCKETS,
    # lifecycle.queue_us keeps DEFAULT_BUCKETS_US (host-latency scale)
}

def declare(registry) -> None:
    """Pin non-default bucket boundaries on `registry`."""
    for name in PERCENT_HISTOGRAMS:
        registry.declare_histogram(name, PERCENT_BUCKETS)
    for name, buckets in _LIFECYCLE_BUCKETS.items():
        registry.declare_histogram(name, buckets)


# Decorated public-API op names (decorator name= or f.__qualname__).
# L005 (analysis/obs_coverage.py) fails CI when a decorated function is
# absent from this set.
API_OPS = frozenset({
    # activation.py
    "silu_and_mul", "gelu_and_mul", "gelu_tanh_and_mul",
    # norm.py
    "rmsnorm", "gemma_rmsnorm", "fused_add_rmsnorm",
    "gemma_fused_add_rmsnorm",
    # rope.py
    "apply_rope", "apply_llama31_rope", "rope_quantize_fp8",
    "mla_rope_quantize_fp8", "rope_quantize_fp8_append_paged_kv_cache",
    # page.py
    "append_paged_kv_cache",
    # decode.py / prefill.py
    "single_decode_with_kv_cache", "single_prefill_with_kv_cache",
    # sampling.py
    "sampling_from_probs", "sampling_from_logits",
    "top_p_sampling_from_probs", "top_k_sampling_from_probs",
    "min_p_sampling_from_probs", "top_k_top_p_sampling_from_probs",
    # serve/engine.py (the continuous-batching engine step)
    "engine.step",
    # serve/kv_tier.py (the tiered-KV movements: host spill/restore +
    # the disaggregated prefill->decode handoff)
    "engine.kv_spill", "engine.kv_restore", "engine.kv_migrate",
    # parallel/plan.py (the mesh-sharded fused serving step)
    "parallel.sharded_step",
})

# The serving subset of the decorated surface: ops that drive whole
# serving steps and therefore MUST open a flight-recorder span
# (obs.spans.SPAN_CATEGORIES declares each one's category).  ``obs
# doctor`` flags any op listed here that spans.SPAN_CATEGORIES does not
# cover — the span-layer extension of the L005 ships-observed rule: a
# new serving op cannot silently ship untraceable.
SERVING_OPS = frozenset({
    "parallel.sharded_step", "engine.step",
    "engine.kv_spill", "engine.kv_restore", "engine.kv_migrate",
})
