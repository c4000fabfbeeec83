"""Step-loop flight deck: host/device overlap profiler on two clocks.

The obs stack meters ops (PR 2/5) and requests (PR 10) but was blind to
the STEP LOOP itself — the host work between device steps that ROADMAP
item 4's pipeline refactor exists to hide.  This module records, for
every serving-step dispatch (``ServingEngine.step`` /
``ShardedServingStep``), one bounded ledger entry:

- named host sub-phase durations (the engine decomposes into ``admit``
  / ``schedule`` / ``assemble`` (schedule-array assembly) / ``lower``
  (kernel-plan lowering via ``build_engine_work_units``) / ``dispatch``
  (signature + host→device upload + the jitted call), then ``fetch``
  (the wait for the sampled tokens) and ``post`` (trie inserts, token
  scatter, page release); the sharded step records ``signature``
  + ``dispatch``).  A phase opened with :meth:`StepTicket.enter` /
  :meth:`StepTicket.phase` is ALSO a ``jax.profiler.TraceAnnotation``
  (``engine.<phase>`` inside one ``engine.step``), so a device trace
  attributes each idle gap to the phase the host was in;
- the device execution window: JAX async dispatch returns before the
  device finishes, so the gate-ON path adds a completion probe
  (``block_until_ready`` — the measurement tax this mode pays) and
  stamps both edges on ``time.perf_counter``, the SAME clock base every
  obs recorder uses, so :func:`trace_events` merges the step lanes into
  the unified chrome trace through ``profiler.perf_to_epoch_us``;
- the derived ``gap_us`` — device idle between step N's completion and
  step N+1's dispatch return, per (surface, thread) lane — from which
  :func:`summarize` computes ``host_frac``, overlap efficiency, and the
  Amdahl projection ``1 / (1 - host_frac)``: the speedup CEILING the
  item-4 two-stage pipeline can buy by hiding host work.

Zero-overhead-by-default: the ``FLASHINFER_TPU_STEPLOOP`` gate lives in
``registry.steploop_enabled`` and the ``obs.steploop_begin`` facade
checks it BEFORE importing this module (the spans/costmodel precedent;
subprocess-pinned by tests/test_steploop.py).  The ledger is a bounded
ring (``FLASHINFER_TPU_STEPLOOP_CAP``, default 2048): the newest N
steps are retained, overwrites are counted, never silent.

Every stamp method takes an optional ``now`` (perf_counter seconds) so
tests can drive hand-computed clocks through the exact production math.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

# Synthetic chrome-trace lanes: host sub-phases and the device window
# ride dedicated tids so they never collide with the per-thread span
# tracks (tid = thread ident) or the ops track (tid = 0).
TRACE_TID_HOST = 0x57E0
TRACE_TID_DEVICE = 0x57E1


def _reg():
    from flashinfer_tpu import obs

    return obs._registry()


def _default_capacity() -> int:
    try:
        return max(1, int(os.environ.get("FLASHINFER_TPU_STEPLOOP_CAP",
                                         "2048")))
    except ValueError:
        return 2048


def _clock(now: Optional[float]) -> float:
    return time.perf_counter() if now is None else float(now)


class _Span:
    """One open phase: a clock window plus the profiler annotation of
    the same interval.  ``close`` books the duration into the ticket."""

    __slots__ = ("tick", "name", "t0", "ann")

    def __init__(self, tick: "StepTicket", name: str, t0: float):
        self.tick, self.name, self.t0 = tick, name, t0
        self.ann = TraceAnnotation(f"{tick.span_prefix}.{name}")
        self.ann.__enter__()

    def close(self, now: Optional[float] = None) -> None:
        self.tick._book(self.name, _clock(now) - self.t0)
        self.ann.__exit__(None, None, None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class StepTicket:
    """One in-flight step measurement.

    Stamp protocol (all perf_counter seconds; contiguous — each
    ``mark`` closes the window since the previous stamp, each ``enter``
    closes the phase still open):

    ``begin() -> (mark(phase) | enter(phase))* -> dispatched() -> done()
    -> commit()``

    ``enter(name)`` opens a top-level phase on the clock AND as a
    profiler span; ``phase(name)`` is a context manager for a child
    span inside it, booked under its dotted name (``lower.build``).
    ``mark`` names a window after the fact, so it has no profiler span.
    ``dispatched()`` closes the ``dispatch`` sub-phase and ends the
    host window; ``done()`` is the completion probe's return (the
    device-window end).  Idle ticks (``commit(idle=True)``) skip
    dispatched/done — an empty-schedule poll has no device lane, and
    the gap math must not mis-attribute it as device time.

    Two books, so that nothing is counted twice: ``phases`` holds the
    top-level phases of the HOST window (before ``dispatched()``) — the
    only ones ``summarize``'s host accounting adds up — and ``spans``
    holds what overlaps or follows them: dotted children, and the
    phases after the dispatch (``fetch``, ``post``).
    """

    __slots__ = ("surface", "tid", "t_begin", "_t_mark", "phases", "spans",
                 "t_dispatch", "t_done", "attrs", "span_prefix", "_open",
                 "_step_ann")

    def __init__(self, surface: str, now: Optional[float] = None,
                 **attrs):
        t = _clock(now)
        self.surface = surface
        self.tid = threading.get_ident()
        self.t_begin = t
        self._t_mark = t
        self.phases: Dict[str, float] = {}
        self.spans: Dict[str, float] = {}
        self.t_dispatch: Optional[float] = None
        self.t_done: Optional[float] = None
        self.attrs = attrs
        self.span_prefix = "engine" if surface == "ServingEngine" \
            else surface
        self._open: Optional[_Span] = None
        self._step_ann = TraceAnnotation(f"{self.span_prefix}.step",
                                         **attrs)
        self._step_ann.__enter__()

    def _book(self, name: str, dur: float) -> None:
        book = self.phases if "." not in name and self.t_dispatch is None \
            else self.spans
        book[name] = book.get(name, 0.0) + dur

    def _close_open(self, t: float) -> None:
        if self._open is not None:
            self._open.close(t)
            self._open = None
            self._t_mark = t

    def mark(self, phase: str, now: Optional[float] = None) -> None:
        """Attribute the window since the previous stamp to ``phase``."""
        t = _clock(now)
        self._book(phase, t - self._t_mark)
        self._t_mark = t

    def enter(self, phase: str, now: Optional[float] = None) -> None:
        """Close the top-level phase still open and open ``phase``."""
        t = _clock(now)
        self._close_open(t)
        self._open = _Span(self, phase, t)

    def phase(self, name: str, now: Optional[float] = None) -> _Span:
        """A child span (``with tick.phase("lower.build"): ...``)."""
        return _Span(self, name, _clock(now))

    def dispatched(self, now: Optional[float] = None) -> None:
        """Async dispatch returned: close the ``dispatch`` sub-phase,
        end the host window, open the device window."""
        t = _clock(now)
        if self._open is not None:
            self._close_open(t)
        else:
            self.mark("dispatch", t)
        self.t_dispatch = t

    def done(self, now: Optional[float] = None) -> None:
        """Completion probe returned: the device window's end."""
        self.t_done = _clock(now)
        self._close_open(self.t_done)

    def commit(self, *, tokens: int = 0, idle: bool = False,
               now: Optional[float] = None, **attrs) -> dict:
        """Close what is open (the last phase, the step's span) and seal
        the ticket into the global ledger; returns the record."""
        self._close_open(_clock(now))
        self._step_ann.__exit__(None, None, None)
        return ledger().commit(self, tokens=tokens, idle=idle,
                               attrs={**self.attrs, **attrs})


class StepLedger:
    """Bounded, thread-safe ring of per-step records (the SpanRecorder
    architecture): the newest ``capacity`` steps are retained,
    overwrites counted via ``dropped``.  ``gap_us`` is derived at
    commit time against the previous committed step of the SAME
    (surface, thread) lane — idle ticks neither produce a gap nor
    break the chain."""

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._buf: List[Optional[dict]] = [None] * self.capacity
        self._lock = threading.Lock()
        self._total = 0
        self._idle_total = 0
        # (surface, tid) -> t_done of the last committed non-idle step
        self._last_done: Dict[tuple, float] = {}

    @property
    def total(self) -> int:
        return self._total

    @property
    def idle_total(self) -> int:
        return self._idle_total

    def dropped(self) -> int:
        return max(0, self._total - self.capacity)

    def commit(self, ticket: StepTicket, *, tokens: int = 0,
               idle: bool = False, attrs: Optional[dict] = None) -> dict:
        host_end = ticket.t_dispatch if ticket.t_dispatch is not None \
            else ticket._t_mark
        rec = {
            "surface": ticket.surface,
            "tid": ticket.tid,
            "idle": bool(idle),
            "tokens": int(tokens),
            "t_begin": ticket.t_begin,
            "t_dispatch": ticket.t_dispatch,
            "t_done": ticket.t_done,
            "phases": dict(ticket.phases),
            "spans": dict(ticket.spans),
            "host_us": (host_end - ticket.t_begin) * 1e6,
            "device_us": None,
            "gap_us": None,
            "attrs": dict(attrs or {}),
        }
        if ticket.t_done is not None and ticket.t_dispatch is not None:
            rec["device_us"] = (ticket.t_done - ticket.t_dispatch) * 1e6
        with self._lock:
            if idle:
                self._idle_total += 1
            elif ticket.t_dispatch is not None:
                key = (ticket.surface, ticket.tid)
                prev_done = self._last_done.get(key)
                if prev_done is not None:
                    rec["gap_us"] = (ticket.t_dispatch - prev_done) * 1e6
                if ticket.t_done is not None:
                    self._last_done[key] = ticket.t_done
            rec["seq"] = self._total
            self._buf[self._total % self.capacity] = rec
            self._total += 1
        _observe_record(rec)
        return rec

    def records(self) -> List[dict]:
        """Retained records, oldest to newest."""
        with self._lock:
            if self._total <= self.capacity:
                return [r for r in self._buf[:self._total]]
            cut = self._total % self.capacity
            return [r for r in self._buf[cut:] + self._buf[:cut]]


def _observe_record(rec: dict) -> None:
    """Mirror one committed record into the metrics registry (the
    steploop gate is already paid — the bench-auditor rule: write
    regardless of FLASHINFER_TPU_METRICS, like the lifecycle
    histograms)."""
    reg = _reg()
    surface = rec["surface"]
    if rec["idle"]:
        reg.counter_inc("steploop.idle_ticks", surface=surface)
        return
    reg.counter_inc("steploop.steps", surface=surface)
    reg.observe("steploop.host_us", rec["host_us"], surface=surface)
    for phase, dur in (*rec["phases"].items(), *rec["spans"].items()):
        reg.observe("steploop.phase_us", dur * 1e6, surface=surface,
                    phase=phase)
    if rec["device_us"] is not None:
        reg.observe("steploop.device_us", rec["device_us"],
                    surface=surface)
    if rec["gap_us"] is not None:
        reg.observe("steploop.gap_us", max(rec["gap_us"], 0.0),
                    surface=surface)


_LEDGER: Optional[StepLedger] = None
_LEDGER_LOCK = threading.Lock()


def ledger() -> StepLedger:
    global _LEDGER
    if _LEDGER is None:
        with _LEDGER_LOCK:
            if _LEDGER is None:
                _LEDGER = StepLedger(_default_capacity())
    return _LEDGER


def reset(capacity: Optional[int] = None) -> None:
    global _LEDGER
    with _LEDGER_LOCK:
        _LEDGER = StepLedger(capacity if capacity is not None
                             else _default_capacity())


def begin(surface: str, now: Optional[float] = None,
          **attrs) -> StepTicket:
    """Open a ticket (callers reach this through ``obs.steploop_begin``,
    which owns the gate check).  ``attrs`` (the engine's ``step`` index)
    go onto the step's profiler span and into the record."""
    return StepTicket(surface, now=now, **attrs)


# ---------------------------------------------------------------------------
# Derived views: summary + unified-trace lanes
# ---------------------------------------------------------------------------


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _dist(vals: List[float]) -> dict:
    s = sorted(vals)
    return {
        "count": len(s),
        "mean": (sum(s) / len(s)) if s else 0.0,
        "p50": _pct(s, 0.50),
        "p90": _pct(s, 0.90),
        "p99": _pct(s, 0.99),
        "max": s[-1] if s else 0.0,
    }


def summarize(records: Optional[List[dict]] = None) -> dict:
    """Aggregate the retained ledger window into the host-loop report
    (``obs doctor`` host_loop section; the selftest's acceptance
    input).

    ``host_frac`` is computed over steady-state lane pairs (records
    carrying a ``gap_us``, i.e. every step after the first per
    (surface, thread) lane): the fraction of the step cadence the
    device spends idle waiting on the host —
    ``Σgap / (Σgap + Σdevice)``.  The Amdahl projection
    ``1 / (1 - host_frac)`` is the speedup CEILING a perfect host/
    device pipeline (ROADMAP item 4) can reach; real wins land below
    it (the host work still exists, it just overlaps).

    The host accounting (``host_us``, ``phases``, ``worst_phase``,
    ``unattributed_frac``) adds up a record's ``phases`` only: its
    ``spans`` (dotted children, ``fetch``, ``post``) lie inside those
    phases or after the host window, and would be counted twice.
    """
    led = ledger()
    recs = led.records() if records is None else list(records)
    steps = [r for r in recs if not r["idle"]]
    idle = [r for r in recs if r["idle"]]
    out = {
        "steps": len(steps),
        "idle_ticks": len(idle),
        "total": led.total if records is None else len(recs),
        "dropped": led.dropped() if records is None else 0,
        "surfaces": sorted({r["surface"] for r in steps}),
    }
    if not steps:
        out.update(host_frac=None, overlap_efficiency=None,
                   amdahl_ceiling=None, negative_gaps=0,
                   missing_device_lane=0, phases={}, worst_phase=None,
                   unattributed_frac=None)
        return out

    host = [r["host_us"] for r in steps]
    device = [r["device_us"] for r in steps if r["device_us"] is not None]
    out["host_us"] = _dist(host)
    out["device_us"] = _dist(device)
    out["missing_device_lane"] = sum(
        1 for r in steps if r["device_us"] is None)

    # steady-state pairs: gap_us present means the lane saw a prior
    # completed step; host_frac pairs each gap with its own step's
    # device window so the two sides cover the same cadence windows
    pairs = [r for r in steps
             if r["gap_us"] is not None and r["device_us"] is not None]
    gaps = [r["gap_us"] for r in pairs]
    out["gap_us"] = _dist(gaps)
    out["negative_gaps"] = sum(1 for g in gaps if g < 0.0)
    gap_sum = sum(max(g, 0.0) for g in gaps)
    dev_sum = sum(r["device_us"] for r in pairs)
    if pairs and (gap_sum + dev_sum) > 0:
        host_frac = gap_sum / (gap_sum + dev_sum)
        out["host_frac"] = host_frac
        out["overlap_efficiency"] = 1.0 - host_frac
        out["amdahl_ceiling"] = 1.0 / max(1.0 - host_frac, 1e-3)
    else:
        out["host_frac"] = None
        out["overlap_efficiency"] = None
        out["amdahl_ceiling"] = None

    phases: Dict[str, float] = {}
    for r in steps:
        for name, dur in r["phases"].items():
            phases[name] = phases.get(name, 0.0) + dur * 1e6
    out["phases"] = {k: round(v, 1) for k, v in sorted(phases.items())}
    out["worst_phase"] = max(phases, key=phases.get) if phases else None
    # host time the named sub-phases did NOT cover (a call site that
    # skipped a mark); contiguous marking keeps this ~0
    unattr = sum(r["host_us"] for r in steps) - sum(phases.values())
    total_host = max(sum(r["host_us"] for r in steps), 1e-9)
    out["unattributed_frac"] = max(unattr, 0.0) / total_host
    return out


def trace_events(records: Optional[List[dict]] = None) -> List[dict]:
    """Chrome-trace events for the retained ledger window, on the
    shared epoch clock base (``profiler.perf_to_epoch_us``) so
    ``export.to_unified_chrome_trace(..., extra_events=...)`` merges
    the step lanes with the span/op tracks: host sub-phases stack on
    the ``steploop host`` lane, device windows ride the ``steploop
    device`` lane, idle ticks land as instant events."""
    from flashinfer_tpu.profiler import perf_to_epoch_us

    pid = os.getpid()
    recs = ledger().records() if records is None else list(records)
    events: List[dict] = [
        {"name": "thread_name", "ph": "M", "pid": pid,
         "tid": TRACE_TID_HOST,
         "args": {"name": "steploop host (sub-phases)"}},
        {"name": "thread_name", "ph": "M", "pid": pid,
         "tid": TRACE_TID_DEVICE,
         "args": {"name": "steploop device (execution windows)"}},
    ]
    for r in recs:
        if r["idle"]:
            events.append({
                "name": f"{r['surface']}.idle", "ph": "i", "s": "t",
                "pid": pid, "tid": TRACE_TID_HOST, "cat": "steploop",
                "ts": perf_to_epoch_us(r["t_begin"]),
            })
            continue
        t = r["t_begin"]
        for phase, dur in r["phases"].items():
            events.append({
                "name": f"{r['surface']}.{phase}", "ph": "X",
                "pid": pid, "tid": TRACE_TID_HOST, "cat": "steploop",
                "ts": perf_to_epoch_us(t), "dur": max(dur, 0.0) * 1e6,
            })
            t += dur
        if r["t_dispatch"] is not None and r["t_done"] is not None:
            events.append({
                "name": f"{r['surface']}.device", "ph": "X",
                "pid": pid, "tid": TRACE_TID_DEVICE, "cat": "steploop",
                "ts": perf_to_epoch_us(r["t_dispatch"]),
                "dur": max(r["t_done"] - r["t_dispatch"], 0.0) * 1e6,
                "args": {"tokens": r["tokens"], "seq": r["seq"]},
            })
    return events
