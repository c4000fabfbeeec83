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

What else happened inside a step is in the step's ONE record (ISSUE
39), so that a slow step can be explained from it alone:

- children of children, handed down as a ``span=`` callable
  (:meth:`StepTicket.under`): ``lower.build.l0`` / ``.l1`` / ``.decode``
  (``build_engine_work_units``; ``lower.build.window.l1`` / ``.decode``
  for a window plan) and ``dispatch.upload.pack`` / ``.put`` / ``.cut``
  (``serve/handover.py``), each also a profiler span;
- the interpreter's collector: one ``gc.callbacks`` hook, installed with
  the process's first ticket, adds a collection's seconds to the open
  ticket's ``spans["gc"]`` (0.0 on a step without one) and counts its
  generation in ``gc_collections``; a generation-2 collection is an
  ``engine.gc`` profiler span too; collections between tickets add to a
  module counter (:func:`summarize`'s ``gc``);
- the compiler: ``jax.monitoring`` listeners, installed with the hook,
  book what fires while a ticket is open: ``spans["compile.trace"]`` /
  ``["compile.lower"]`` / ``["compile.backend"]`` (the UNION of each
  kind's time spans: a jit traced inside the step's body fires inside
  the outer one's), ``["compile.cache_load"]`` and the persistent
  cache's hits and misses.  Such a step, and any step that traced, also
  leaves a line in the :func:`compile_log`, which outlives
  :func:`reset` (set-up is read after the window) and replaces the
  ``ServingEngine.trace_and_compile`` span of the SPANS gate;
- ``t_end``, the commit's stamp, so the record covers ``step()`` to its
  return, :func:`slowest` orders the retained records by it, and at
  process exit, under the gate, the slowest steady steps go to stderr
  (unless the caller reset the ledger: it then reads it itself).

Every stamp method takes an optional ``now`` (perf_counter seconds) so
tests can drive hand-computed clocks through the exact production math.
"""

from __future__ import annotations

import atexit
import collections
import gc
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

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


# the ticket open on each thread: what the collector's hook and the
# compiler's listeners book into
_OPEN = threading.local()

# jax.monitoring's names -> the record's
COMPILE_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_COUNT_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                      "/jax/compilation_cache/cache_misses": "misses"}
COMPILE_KEYS = (*COMPILE_SPAN_EVENTS.values(), "compile.cache_load")
COMPILE_LOG_CAP = 1024


def _union_s(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by ``intervals``, each second once."""
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total


class _Compiles:
    """What the compiler's events said while one ticket was open."""

    __slots__ = ("spans", "cache_load", "funs", "counts")

    def __init__(self):
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.cache_load = 0.0
        self.funs: List[str] = []
        self.counts = dict.fromkeys(CACHE_COUNT_EVENTS.values(), 0)

    def seconds(self) -> Dict[str, float]:
        out = {k: _union_s(self.spans.get(k, ()))
               for k in COMPILE_SPAN_EVENTS.values()}
        out["compile.cache_load"] = self.cache_load
        return out


def _compiles() -> Optional[_Compiles]:
    tick = getattr(_OPEN, "tick", None)
    if tick is None:
        return None
    if tick.compiles is None:
        tick.compiles = _Compiles()
    return tick.compiles


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kwargs) -> None:
    kind = COMPILE_SPAN_EVENTS.get(event)
    c = _compiles() if kind is not None else None
    if c is not None:
        c.spans.setdefault(kind, []).append((start_time, end_time))
        # the programs' names: a trace event fires for every jitted
        # function traced inside one, a lowering or a compile per program
        fun = kwargs.get("fun_name")
        if kind != "compile.trace" and fun is not None \
                and fun not in c.funs:
            c.funs.append(str(fun))


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    c = _compiles() if event == CACHE_LOAD_EVENT else None
    if c is not None:
        c.cache_load += duration_secs


def _on_event(event: str, **kwargs) -> None:
    key = CACHE_COUNT_EVENTS.get(event)
    c = _compiles() if key is not None else None
    if c is not None:
        c.counts[key] += 1


class _Collector:
    """The ``gc.callbacks`` hook.  A collection belongs to the ticket that
    is open on its thread when it STARTS; one that starts between
    tickets adds to ``outside_s`` / ``outside``."""

    def __init__(self):
        self._t0: Optional[float] = None
        self._tick: Optional["StepTicket"] = None
        self._ann: Optional[TraceAnnotation] = None
        self.clear()

    def clear(self) -> None:
        self.outside_s = 0.0
        self.outside = [0, 0, 0]

    def __call__(self, phase: str, info: dict,
                 now: Optional[float] = None) -> None:
        if phase == "start":
            self._t0 = _clock(now)
            self._tick = getattr(_OPEN, "tick", None)
            if info["generation"] == 2:
                self._ann = TraceAnnotation("engine.gc")
                self._ann.__enter__()
        elif self._t0 is not None:
            dur, gen = _clock(now) - self._t0, info["generation"]
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            if self._tick is not None:
                self._tick.gc_s += dur
                self._tick.gc_collections[gen] += 1
            else:
                self.outside_s += dur
                self.outside[gen] += 1
            self._t0 = self._tick = self._ann = None


_collector = _Collector()
_INSTALLED = False
# a caller that resets the ledger reads it itself (perfbench/run.py in a
# traced run, whose last lines on stderr are the check's): no exit report
_RESET_SEEN = False


def _install() -> None:
    """Once a process, with its first ticket: the collector's hook, the
    compiler's listeners and the exit report."""
    global _INSTALLED
    with _LEDGER_LOCK:
        if _INSTALLED:
            return
        _INSTALLED = True
    from jax import monitoring

    gc.callbacks.append(_collector)
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    atexit.register(_at_exit)


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
    ``under(parent)`` is what a callee takes as its ``span=``: its short
    names open children of ``parent`` (``lower.build`` -> ``lower.build.l0``).
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
    phases after the dispatch (``fetch``, ``post``), and at the commit
    ``gc`` and, where the compiler's events fired, ``compile.*``.
    """

    __slots__ = ("surface", "tid", "t_begin", "_t_mark", "phases", "spans",
                 "t_dispatch", "t_done", "t_end", "attrs", "span_prefix",
                 "_open", "_step_ann", "gc_s", "gc_collections", "compiles")

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
        self.t_end: Optional[float] = None
        self.gc_s = 0.0
        self.gc_collections = [0, 0, 0]
        self.compiles: Optional[_Compiles] = None
        self.attrs = attrs
        self.span_prefix = "engine" if surface == "ServingEngine" \
            else surface
        self._open: Optional[_Span] = None
        self._step_ann = TraceAnnotation(f"{self.span_prefix}.step",
                                         **attrs)
        self._step_ann.__enter__()
        _OPEN.tick = self

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

    def under(self, parent: str) -> Callable[[str], _Span]:
        """The ``span=`` of a callee that runs inside ``parent``."""
        return lambda name: self.phase(f"{parent}.{name}")

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
        """Close what is open (the last phase, the step's span), book
        what the collector and the compiler said, and seal the ticket
        into the global ledger; returns the record."""
        self.t_end = _clock(now)
        self._close_open(self.t_end)
        self._step_ann.__exit__(None, None, None)
        if getattr(_OPEN, "tick", None) is self:
            _OPEN.tick = None
        self.spans["gc"] = self.gc_s
        if self.compiles is None and attrs.get("traced"):
            self.compiles = _Compiles()     # traced: a line in the log
        if self.compiles is not None:
            self.spans.update(self.compiles.seconds())
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
            "t_end": ticket.t_end,
            "gc_collections": list(ticket.gc_collections),
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
        rec["compiled"] = ticket.compiles is not None
        if rec["compiled"]:
            _log_compile(rec, ticket.compiles)
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


# steps that traced or during which the compiler's events fired, oldest
# to newest; outlives reset(), which a harness calls before its window
_COMPILE_LOG: collections.deque = collections.deque(maxlen=COMPILE_LOG_CAP)


def _log_compile(rec: dict, c: _Compiles) -> None:
    a, sp = rec["attrs"], rec["spans"]
    _COMPILE_LOG.append({
        "surface": rec["surface"], "step": a.get("step"),
        "rung": a.get("rung"), "traced": bool(a.get("traced")),
        "funs": list(c.funs), **c.seconds(),
        "dispatch.call": sp.get("dispatch.call"), "fetch": sp.get("fetch"),
        **c.counts,
        "t_begin": rec["t_begin"], "t_end": rec["t_end"]})


def compile_log() -> List[dict]:
    """One line for each step that traced or compiled, the newest
    ``COMPILE_LOG_CAP``: surface, the engine's ``step`` index and
    ``rung``, ``traced``, the ``funs`` the compiler named, seconds of
    ``compile.trace`` / ``.lower`` / ``.backend`` / ``.cache_load``, of
    the jitted call (``dispatch.call``) and of the program's first run
    (``fetch``), the persistent cache's ``hits`` and ``misses``, and the
    step's ``t_begin`` / ``t_end``."""
    return list(_COMPILE_LOG)


def reset(capacity: Optional[int] = None, compile_log: bool = False) -> None:
    """A fresh ledger and collector count; the compile log stays unless
    ``compile_log`` is true.  The caller reads the ledger itself from
    here on: the process writes no exit report."""
    global _LEDGER, _RESET_SEEN
    _RESET_SEEN = True
    with _LEDGER_LOCK:
        _LEDGER = StepLedger(capacity if capacity is not None
                             else _default_capacity())
    _collector.clear()
    if compile_log:
        _COMPILE_LOG.clear()


def begin(surface: str, now: Optional[float] = None,
          **attrs) -> StepTicket:
    """Open a ticket (callers reach this through ``obs.steploop_begin``,
    which owns the gate check).  ``attrs`` (the engine's ``step`` index)
    go onto the step's profiler span and into the record.  The first one
    of a process installs the collector's hook, the compiler's listeners
    and the exit report."""
    if not _INSTALLED:
        _install()
    return StepTicket(surface, now=now, **attrs)


def slowest(k: int = 3, steady: bool = False) -> List[dict]:
    """The ``k`` retained records with the longest ``t_end - t_begin``,
    longest first.  ``steady`` leaves out the steps of the compile log
    (a rung's first step takes seconds and is no stall)."""
    recs = [r for r in ledger().records()
            if not r["idle"] and not (steady and r["compiled"])]
    return sorted(recs, key=lambda r: r["t_begin"] - r["t_end"])[:k]


def describe(rec: dict) -> str:
    """One line for one record: what it was, its wall time, then every
    phase and span it holds in milliseconds."""
    a = rec["attrs"]
    ms = " ".join(f"{n} {1e3 * t:.2f}" for book in ("phases", "spans")
                  for n, t in rec[book].items())
    return (f"{rec['surface']} step {a.get('step')} rung {a.get('rung')} "
            f"tokens {rec['tokens']} traced {bool(a.get('traced'))}: "
            f"{1e3 * (rec['t_end'] - rec['t_begin']):.2f} ms = {ms}; "
            "collections " + "/".join(map(str, rec["gc_collections"])))


def _at_exit() -> None:
    if not _RESET_SEEN:
        _exit_report()


def _exit_report() -> None:
    """Under the gate, at the exit of a process that never called
    :func:`reset`: the three slowest steady steps and the compile log's
    totals, a line each on stderr, so that an untraced run names the phase
    of a stall."""
    from flashinfer_tpu import obs

    if not obs.steploop_enabled():
        return
    for i, rec in enumerate(slowest(3, steady=True), 1):
        print(f"[steploop] slowest {i}: {describe(rec)}", file=sys.stderr)
    log = compile_log()
    if log:
        tot = {k: sum(ln[k] for ln in log) for k in COMPILE_KEYS}
        tot["first runs"] = sum(ln["fetch"] or 0.0 for ln in log)
        wall = sum(ln["t_end"] - ln["t_begin"] for ln in log)
        print(f"[steploop] compile log: {len(log)} steps, the last step "
              f"{log[-1]['step']}, {wall:.2f} s in them; seconds "
              + ", ".join(f"{k} {v:.2f}" for k, v in tot.items()),
              file=sys.stderr)


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
    ``spans`` (dotted children, ``fetch``, ``post``, ``gc``,
    ``compile.*``) lie inside those phases or after the host window, and
    would be counted twice.  ``gc`` is the collector's time inside the
    steps' tickets and between them (since the last :func:`reset`);
    ``compile_log_lines`` counts :func:`compile_log`.
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
        # the collector inside the steps' tickets and between them
        # (seconds, collections a generation), and the compile log
        "gc": {"in_steps_s": sum(r["spans"]["gc"] for r in recs),
               "in_steps": [sum(r["gc_collections"][g] for r in recs)
                            for g in range(3)],
               "outside_s": _collector.outside_s,
               "outside": list(_collector.outside)},
        "compile_log_lines": len(_COMPILE_LOG),
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
