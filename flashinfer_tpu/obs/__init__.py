"""flashinfer_tpu.obs — unified runtime observability.

The metrics half of the observability layer (the tracing half lives in
``profiler.py`` / ``api_logging.py`` / ``trace.py``; this package ties
all of them together).  Components:

- :mod:`~flashinfer_tpu.obs.registry` — process-wide thread-safe
  counters / gauges / fixed-bucket histograms, gated by
  ``FLASHINFER_TPU_METRICS`` (default off, no-op-cheap);
- :mod:`~flashinfer_tpu.obs.catalog` — the authoritative metric list
  (names, types, labels), cross-checked against the decorated public
  API by the L005 analysis pass;
- :mod:`~flashinfer_tpu.obs.export` — JSON snapshot, Prometheus text
  format, and chrome-trace merge of the op timeline;
- :mod:`~flashinfer_tpu.obs.bench_audit` — the self-auditing bench
  telemetry (row quality stamps vs BENCH_BANKED.md history, raw +
  roofline-fraction spaces);
- :mod:`~flashinfer_tpu.obs.hwspec` — the chip-spec registry (peak
  HBM/MXU/VMEM/ICI per generation; the single source of truth);
- :mod:`~flashinfer_tpu.obs.costmodel` — analytic FLOPs/bytes per op
  family (NOT imported here: the zero-overhead test pins that plain
  library use never loads it);
- :mod:`~flashinfer_tpu.obs.roofline` — cost x wall time x spec ->
  ``pct_roofline`` attribution + the ``obs perf`` report builder;
- ``python -m flashinfer_tpu.obs`` — ``report`` / ``doctor`` /
  ``perf`` CLI.

Call-site contract: the module-level helpers below apply the metrics
gate themselves, so instrumentation reads as one line
(``obs.counter_inc("plan.calls", wrapper=...)``) and costs one function
call + one env lookup when disabled.  Hot paths that need the gate
folded into an existing branch (the ``@flashinfer_api`` fast path) use
:func:`metrics_enabled` directly.

See docs/observability.md for the full catalog and env-var matrix.
"""

from __future__ import annotations

import contextlib

from flashinfer_tpu.obs import catalog
from flashinfer_tpu.obs.registry import (Registry, get, metrics_enabled,
                                         spans_enabled, steploop_enabled)

__all__ = [
    "Registry", "get", "metrics_enabled", "spans_enabled",
    "steploop_enabled", "catalog",
    "counter_inc", "gauge_set", "observe", "record_plan",
    "record_dropped_tokens", "snapshot", "reset",
    "span", "record_retrace", "state_signature", "diff_statics",
    "diff_state_sigs", "record_span",
    "request_begin", "prefill_chunk", "decode_step", "request_finish",
    "lifecycle_snapshot",
    "steploop_begin", "steploop_summary", "child_span",
]

_declared = False


def _registry() -> Registry:
    global _declared
    reg = get()
    if not _declared:
        catalog.declare(reg)
        _declared = True
    return reg


def counter_inc(name: str, value: int = 1, **labels) -> int:
    """Gated counter increment; returns the new total (0 when gated
    off, so callers can't misread a disabled counter as progress)."""
    if not metrics_enabled():
        return 0
    return _registry().counter_inc(name, value, **labels)


def gauge_set(name: str, value: float, **labels) -> None:
    if metrics_enabled():
        _registry().gauge_set(name, value, **labels)


def observe(name: str, value: float, **labels) -> None:
    if metrics_enabled():
        _registry().observe(name, value, **labels)


def record_plan(wrapper, *, replan: bool, padded_vs_actual=(),
                statics=None) -> None:
    """Plan-lifecycle wiring shared by the decode/prefill/attention
    wrappers: one call per plan() with the padding-waste pairs.

    ``padded_vs_actual``: iterable of ``(axis_name, padded, actual)``.
    ``statics``: the NEW frozen plan (dataclass/dict) — with the spans
    gate on, replans diff it against the wrapper's previous plan and
    attribute the retrace cause (obs.spans.note_plan); with the gate
    off it costs nothing and loads nothing.
    """
    if metrics_enabled():
        reg = _registry()
        name = type(wrapper).__name__
        reg.counter_inc("plan.calls", wrapper=name)
        if replan:
            reg.counter_inc("plan.replans", wrapper=name)
        for axis, padded, actual in padded_vs_actual:
            if padded > 0:
                reg.observe(
                    "plan.padding_waste_pct",
                    100.0 * (1.0 - float(actual) / float(padded)),
                    wrapper=name, axis=axis,
                )
    if statics is not None and spans_enabled():
        from flashinfer_tpu.obs import spans as _spans

        _spans.note_plan(wrapper, replan=replan, statics=statics)


def record_dropped_tokens(dropped, dispatch: str) -> int:
    """Count capacity-dropped MoE routes when the count is CONCRETE.

    ``fused_moe_ep`` computes ``dropped`` on device; under jit /
    shard_map it is a tracer and cannot be read host-side — those calls
    are skipped (the caller still gets the array via
    ``return_dropped=True``).  Eager calls (tests, debugging, capacity
    sizing sweeps) land in the counter.  Returns the count recorded
    (0 when gated off, skipped, or zero-drop).
    """
    if not metrics_enabled():
        return 0
    try:
        import jax

        if isinstance(dropped, jax.core.Tracer):
            return 0
        n = int(jax.numpy.sum(dropped))
    except Exception:
        return 0
    if n:
        _registry().counter_inc("moe.dropped_tokens", n, dispatch=dispatch)
    return n


def snapshot() -> dict:
    """JSON-ready snapshot of everything recorded (works regardless of
    the gate — you can read out what an enabled phase recorded after
    flipping the env var back off)."""
    return _registry().snapshot()


def reset() -> None:
    _registry().reset()


# ---------------------------------------------------------------------------
# Flight-recorder facade (obs.spans; FLASHINFER_TPU_SPANS gate).
# Every helper below checks the gate BEFORE importing the spans module,
# so plain library use never loads it (the subprocess pin in
# tests/test_obs_spans.py) and an instrumented call site reads as one
# line, the same contract as the metric helpers above.
# ---------------------------------------------------------------------------

_NULL_SPAN = contextlib.nullcontext()  # reusable + reentrant


def span(name: str, cat: str = "host", **attrs):
    """Nested host-side span context manager (no-op when gated off)."""
    if not spans_enabled():
        return _NULL_SPAN
    from flashinfer_tpu.obs import spans as _spans

    return _spans.span(name, cat, **attrs)


def state_signature(tree, names=None):
    """Trace signature (structure + shape/dtype) of a run-state pytree,
    or None when the spans gate is off — callers keep it per step and
    diff it on retrace (serve/step.py, parallel/plan.py)."""
    if not spans_enabled():
        return None
    from flashinfer_tpu.obs import spans as _spans

    return _spans.state_signature(tree, names)


def diff_statics(old, new):
    """Diff two plan signatures ({key: summary} dicts); {} when the
    spans gate is off (never imports the machinery, like every helper
    here)."""
    if not spans_enabled():
        return {}
    from flashinfer_tpu.obs import spans as _spans

    return _spans.diff_statics(old, new)


def diff_state_sigs(old, new, tree):
    """Diff two run-state signatures (obs.state_signature results),
    rendering readable leaf keys from ``tree`` — retrace-path only."""
    if not spans_enabled():
        return {}
    from flashinfer_tpu.obs import spans as _spans

    return _spans.diff_state_sigs(old, new, tree)


def record_retrace(wrapper_name: str, changed: dict) -> None:
    """Attribute one retrace: a flight-recorder span with the full
    static diff + `plan.retrace_cause{wrapper,key}` counts per key."""
    if not spans_enabled():
        return
    from flashinfer_tpu.obs import spans as _spans

    _spans.record_retrace(wrapper_name, changed)


def record_span(name: str, cat: str, t0: float, t1: float,
                **attrs) -> None:
    """Record a completed span over an already-measured [t0, t1]
    perf_counter window (no-op when gated off) — for call sites that
    time the work themselves, e.g. the serving steps' trace+compile
    span over a dispatch that traced."""
    if spans_enabled():
        from flashinfer_tpu.obs import spans as _spans

        _spans.record(name, cat, t0, t1, **attrs)


def request_begin(rid: str, **kw) -> None:
    if spans_enabled():
        from flashinfer_tpu.obs import spans as _spans

        _spans.request_begin(rid, **kw)


def prefill_chunk(rid: str, num_tokens: int, **kw) -> None:
    if spans_enabled():
        from flashinfer_tpu.obs import spans as _spans

        _spans.prefill_chunk(rid, num_tokens, **kw)


def decode_step(rid: str, num_tokens: int = 1, **kw) -> None:
    if spans_enabled():
        from flashinfer_tpu.obs import spans as _spans

        _spans.decode_step(rid, num_tokens, **kw)


def request_finish(rid: str, **kw):
    """Close a request's lifecycle; returns the per-request summary
    dict (tokens, ttft_us, tokens_per_s, ...) or None when gated off."""
    if not spans_enabled():
        return None
    from flashinfer_tpu.obs import spans as _spans

    return _spans.request_finish(rid, **kw)


def lifecycle_snapshot():
    """The lifecycle histograms (TTFT/TPOT/queue/tok-s) unflattened, or
    {} when gated off — the per-run summary examples/generate.py
    prints."""
    if not spans_enabled():
        return {}
    from flashinfer_tpu.obs import spans as _spans

    return _spans.lifecycle_snapshot()


# ---------------------------------------------------------------------------
# Step-loop flight deck facade (obs.steploop; FLASHINFER_TPU_STEPLOOP
# gate).  Same contract as the spans facade above: the gate is checked
# BEFORE the module is imported, so plain library use never loads the
# ledger (the zero-overhead subprocess pin in tests/test_steploop.py)
# and a gated-off step surface pays one function call + one env lookup
# + one `if tick is not None` branch per stamp.
# ---------------------------------------------------------------------------


def steploop_begin(surface: str, **attrs):
    """Open a step-loop ticket for one serving-step dispatch, or None
    when the gate is off — call sites keep the ticket local and guard
    every stamp with ``if tick is not None`` (see serve/step.py).
    ``attrs`` label the step's profiler span and its ledger record."""
    if not steploop_enabled():
        return None
    from flashinfer_tpu.obs import steploop as _steploop

    return _steploop.begin(surface, **attrs)


def steploop_summary():
    """The aggregated host-loop report over the retained ledger window
    (host_frac, worst sub-phase — obs.steploop.summarize),
    or None when the gate is off."""
    if not steploop_enabled():
        return None
    from flashinfer_tpu.obs import steploop as _steploop

    return _steploop.summarize()


_NO_SPAN = contextlib.nullcontext()


def child_span(span, name: str):
    """``span(name)``, or a context manager that does nothing where
    ``span`` is None: how a callee of a step surface opens a child of the
    caller's phase (``StepTicket.under``) at no cost with the gate off."""
    return _NO_SPAN if span is None else span(name)
