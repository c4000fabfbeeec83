"""Serving flight recorder: bounded ring-buffer span recorder +
request-lifecycle metering + retrace-cause attribution.

The tracing half of the request-level observability story (ISSUE 10):
where the registry answers "how often / how long" per op and the
profiler timeline answers "what op ran when", this module answers the
serving engine's questions — *what happened to request R* (queue wait,
TTFT, per-token cadence) and *why did the serving step retrace* (which
frozen static moved).  The reference amortizes exactly these host
costs through its plan/run lifecycle + CUDAGraph capture; on TPU the
recompile is the silent analog, so every retrace carries a structured
diff of the statics that changed (well-defined because L003 freezes
them host-side).

Contracts (same standard as the metrics registry):

- **Zero-overhead-by-default.**  Everything here is gated by
  ``FLASHINFER_TPU_SPANS`` (default off).  The gate itself lives in
  ``obs.registry.spans_enabled`` and the facade helpers in
  ``flashinfer_tpu.obs`` check it BEFORE importing this module — plain
  library use never loads the spans machinery at all (subprocess-pinned
  by ``tests/test_obs_spans.py``, the ``obs.costmodel`` precedent).
- **Bounded.**  The recorder is a ring buffer (capacity
  ``FLASHINFER_TPU_SPANS_CAP``, default 4096): a long-lived serving
  process records forever and keeps the most recent window — a flight
  recorder, not an unbounded log.  Overwrites are counted
  (``dropped()``), never silent.
- **Thread-safe.**  One lock per recorder around every mutation and
  drain; the nesting stack is thread-local, so executor threads nest
  their own spans without cross-talk (the profiler-timeline lesson).
- **One clock.**  Span timestamps are ``time.perf_counter`` values
  converted through ``profiler.perf_to_epoch_us`` at export time — the
  SAME anchor the op timeline uses, so the unified chrome trace nests
  spans and op events on one timeline (the epoch-vs-perf_counter skew
  fix, ISSUE 10 satellite).

Metric side effects (lifecycle histograms, ``plan.retrace_cause``
counters) write straight into the registry regardless of
``FLASHINFER_TPU_METRICS`` — once the spans gate is paid the slow path
is already bought, the same rule the api-log call index and the bench
auditor follow (registry.py module docstring).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

# categories a span may carry (the chrome-trace ``cat`` field); the
# request-lifecycle phases are the ISSUE 10 tentpole set, ``steploop``
# is the step-loop flight deck's lane category (obs.steploop emits its
# host/device lanes with it so unified-trace tooling can filter them)
SPAN_CATEGORIES_VALID = frozenset({
    "plan", "trace", "compile", "dispatch", "request", "prefill",
    "decode", "retrace", "host", "steploop",
})

# Serving-op -> span category: the span analog of
# ``costmodel.API_OP_COSTS`` — ``obs doctor`` flags any op in
# ``catalog.SERVING_OPS`` missing here (a serving op that opens no
# span), extending the L005 ships-observed rule to the flight recorder.
SPAN_CATEGORIES: Dict[str, str] = {
    "parallel.sharded_step": "dispatch",
    "engine.step": "dispatch",
    # tiered-KV movements (serve/kv_tier.py): host-side page copies
    "engine.kv_spill": "host",
    "engine.kv_restore": "host",
    "engine.kv_migrate": "host",
}

# small plan arrays get a content fingerprint in plan signatures (value
# changes of closed arrays force retraces too); big run-state arrays
# never do — retraces depend only on structure/shape/dtype
_FINGERPRINT_MAX_ELEMS = 4096
_SIG_DEPTH_MAX = 4


def _reg():
    """The declared global registry (the obs facade's, so the catalog
    bucket pins apply to the lifecycle histograms)."""
    from flashinfer_tpu import obs

    return obs._registry()


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Span:
    """One recorded host-side span (``dur == 0.0`` for instants)."""

    name: str
    cat: str
    ts: float  # time.perf_counter seconds at span start
    dur: float  # seconds
    tid: int
    span_id: int
    parent_id: Optional[int]
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "cat": self.cat, "ts": self.ts,
            "dur": self.dur, "tid": self.tid, "span_id": self.span_id,
            "parent_id": self.parent_id, "attrs": self.attrs,
        }


def _default_capacity() -> int:
    try:
        return max(int(os.environ.get("FLASHINFER_TPU_SPANS_CAP",
                                      "4096")), 1)
    except ValueError:
        return 4096


class SpanRecorder:
    """Process-wide bounded ring buffer of :class:`Span` records.

    ``record`` overwrites the oldest entry once ``capacity`` is
    reached; ``total`` keeps the lifetime count so ``dropped`` is
    always exact (the ring-bound pin in tests/test_obs_spans.py)."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = int(capacity) if capacity else _default_capacity()
        self._lock = threading.Lock()
        self._buf: List[Optional[Span]] = []
        self._total = 0
        self._next_id = 0

    def next_span_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    def record(self, span: Span) -> None:
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(span)
            else:
                self._buf[self._total % self.capacity] = span
            self._total += 1

    @property
    def total(self) -> int:
        with self._lock:
            return self._total

    def dropped(self) -> int:
        with self._lock:
            return max(0, self._total - self.capacity)

    def spans(self) -> List[Span]:
        """Oldest-to-newest copy of the retained window."""
        with self._lock:
            if self._total <= self.capacity:
                return list(self._buf)
            cut = self._total % self.capacity
            return list(self._buf[cut:]) + list(self._buf[:cut])

    def reset(self) -> None:
        with self._lock:
            self._buf = []
            self._total = 0


_recorder: Optional[SpanRecorder] = None
_recorder_lock = threading.Lock()
_tls = threading.local()


def get_recorder() -> SpanRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = SpanRecorder()
    return _recorder


def reset(capacity: Optional[int] = None) -> None:
    """Drop all recorded spans and in-flight request state; a non-None
    ``capacity`` rebuilds the ring at that size (tests)."""
    global _recorder
    with _recorder_lock:
        _recorder = SpanRecorder(capacity)
    with _req_lock:
        _requests.clear()


def drain() -> List[dict]:
    """The retained window as JSON-ready dicts, oldest first."""
    return [s.to_dict() for s in get_recorder().spans()]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def record(name: str, cat: str, t0: float, t1: float, **attrs) -> Span:
    """Record a completed span [t0, t1] (perf_counter seconds).  The
    parent is whatever span is OPEN on this thread — a flat record from
    inside a ``span()`` region nests correctly without pushing."""
    rec = get_recorder()
    st = _stack()
    sp = Span(name=name, cat=cat, ts=float(t0),
              dur=max(float(t1) - float(t0), 0.0),
              tid=threading.get_ident(), span_id=rec.next_span_id(),
              parent_id=st[-1] if st else None, attrs=attrs)
    rec.record(sp)
    return sp


def record_instant(name: str, cat: str, t: Optional[float] = None,
                   **attrs) -> Span:
    t = time.perf_counter() if t is None else float(t)
    return record(name, cat, t, t, **attrs)


@contextlib.contextmanager
def span(name: str, cat: str = "host", **attrs) -> Iterator[None]:
    """Nested host-side span: pushes onto the thread-local stack so
    inner spans (and flat :func:`record` calls) parent under it."""
    rec = get_recorder()
    sid = rec.next_span_id()
    st = _stack()
    parent = st[-1] if st else None
    st.append(sid)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        st.pop()
        rec.record(Span(name=name, cat=cat, ts=t0, dur=t1 - t0,
                        tid=threading.get_ident(), span_id=sid,
                        parent_id=parent, attrs=attrs))


# ---------------------------------------------------------------------------
# static signatures + retrace-cause diffs
# ---------------------------------------------------------------------------


def _leaf_summary(x, fingerprint: bool) -> str:
    """One stable string per static: arrays render as ``dtype[shape]``
    (plus a content digest for small plan arrays when asked), scalars
    as their repr — the L003 statics in comparable form."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        s = f"{dtype}[{','.join(str(d) for d in shape)}]"
        if fingerprint:
            try:
                import numpy as np

                arr = np.asarray(x)
                if arr.size <= _FINGERPRINT_MAX_ELEMS:
                    s += "#" + hashlib.sha1(
                        arr.tobytes()).hexdigest()[:8]
            except Exception:
                pass  # a non-materializable leaf keeps shape/dtype only
        return s
    return repr(x)[:120]


def _walk(obj, prefix: str, out: Dict[str, str], depth: int,
          fingerprint: bool) -> None:
    if depth > _SIG_DEPTH_MAX:
        out[prefix or "<root>"] = repr(obj)[:120]
        return
    if getattr(obj, "shape", None) is not None \
            and getattr(obj, "dtype", None) is not None:
        out[prefix or "<root>"] = _leaf_summary(obj, fingerprint)
        return
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        for f in dataclasses.fields(obj):
            key = f"{prefix}.{f.name}" if prefix else f.name
            _walk(getattr(obj, f.name), key, out, depth + 1, fingerprint)
        return
    if isinstance(obj, dict):
        for k, v in obj.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            _walk(v, key, out, depth + 1, fingerprint)
        return
    if isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _walk(v, f"{prefix}[{i}]", out, depth + 1, fingerprint)
        return
    out[prefix or "<root>"] = repr(obj)[:120]


def plan_signature(statics) -> Dict[str, str]:
    """Flatten a frozen plan (dataclass / dict / nested containers)
    into ``{dotted.field: summary}`` — small closed arrays carry a
    content digest because a value change of an HLO-embedded constant
    retraces just like a shape change."""
    out: Dict[str, str] = {}
    _walk(statics, "", out, 0, fingerprint=True)
    return out


@dataclasses.dataclass
class _StateSig:
    """Cheap per-step trace signature of a run-state pytree: the
    treedef plus raw ``(shape, dtype)`` per leaf — attribute reads
    only, NO string rendering on the hot serving path (readable keys
    are built lazily by :func:`diff_state_sigs`, on the rare retrace
    path).  Holds no array references, so keeping one per wrapper
    never pins a donated buffer."""

    treedef: object
    names: Optional[Tuple[str, ...]]
    leaves: tuple  # per-leaf (shape-tuple | None, dtype | repr)


def _leaf_sig(leaf) -> tuple:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), dtype)
    return (None, repr(leaf)[:80])


def state_signature(tree, names: Optional[Tuple[str, ...]] = None
                    ) -> _StateSig:
    """Trace signature of a RUN-state pytree: structure + shape/dtype
    per leaf, NO value fingerprints (jit retraces on structure/shape/
    dtype only; cache-scale arrays must never transfer host-side).
    ``names`` labels the components of a top-level tuple state in the
    readable diff."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return _StateSig(treedef=treedef, names=names,
                     leaves=tuple(_leaf_sig(l) for l in leaves))


def _render_leaf(sig_leaf: tuple) -> str:
    shape, dtype = sig_leaf
    if shape is None:
        return str(dtype)
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def _leaf_keys(tree, names: Optional[Tuple[str, ...]]) -> List[str]:
    """Readable per-leaf keys (``logits``, ``caches[0][1]``,
    ``params.layers[3]['q_proj']``...) — the expensive path-walk,
    done only when a retrace needs attributing."""
    import jax

    keys = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if names is not None and path \
                and isinstance(path[0], jax.tree_util.SequenceKey) \
                and path[0].idx < len(names):
            keys.append(names[path[0].idx]
                        + jax.tree_util.keystr(path[1:]))
        else:
            keys.append(jax.tree_util.keystr(path))
    return keys


def diff_state_sigs(old: Optional[_StateSig], new: _StateSig,
                    tree) -> Dict[str, Tuple[Any, Any]]:
    """Diff two run-state signatures; ``tree`` is the CURRENT state
    (same structure as ``new``), used to render readable keys on this
    rare path.  Same old-is-None contract as :func:`diff_statics`."""
    if old is None:
        return {"<unattributed: no prior signature>": (None, None)}
    if old.treedef != new.treedef:
        return {"pytree_structure": (str(old.treedef)[:120],
                                     str(new.treedef)[:120])}
    idxs = [i for i, (a, b) in enumerate(zip(old.leaves, new.leaves))
            if a != b]
    if not idxs:
        return {}
    keys = _leaf_keys(tree, new.names)
    return {keys[i]: (_render_leaf(old.leaves[i]),
                      _render_leaf(new.leaves[i])) for i in idxs}


def diff_statics(old: Optional[Dict[str, str]],
                 new: Dict[str, str]) -> Dict[str, Tuple[Any, Any]]:
    """``{key: (old, new)}`` for every static that moved.  ``old`` may
    be None (spans enabled after the previous trace) — the retrace is
    then real but unattributable, reported under one explicit key
    rather than a misleading everything-changed diff."""
    if old is None:
        return {"<unattributed: no prior signature>": (None, None)}
    changed: Dict[str, Tuple[Any, Any]] = {}
    for k in sorted(set(old) | set(new)):
        a, b = old.get(k, "<absent>"), new.get(k, "<absent>")
        if a != b:
            changed[k] = (a, b)
    return changed


def record_retrace(wrapper_name: str,
                   changed: Dict[str, Tuple[Any, Any]], *,
                   kind: str = "retrace") -> None:
    """One retrace (or replan-with-changed-statics) event: a span in
    the flight recorder carrying the full diff, plus one
    ``plan.retrace_cause{wrapper,key}`` count per changed static — the
    counters behind ``obs doctor``'s ranked top-retrace-causes table."""
    if not changed:
        changed = {"<unattributed: statics identical>": (None, None)}
    reg = _reg()
    for key in changed:
        reg.counter_inc("plan.retrace_cause", wrapper=wrapper_name,
                        key=key)
    record_instant(
        f"{wrapper_name}.{kind}", "retrace", wrapper=wrapper_name,
        kind=kind,
        changed={k: [str(a), str(b)] for k, (a, b) in changed.items()})


def note_plan(wrapper, *, replan: bool, statics) -> None:
    """Plan-lifecycle hook (called from ``obs.record_plan`` when the
    spans gate is on): record a plan span, and on a replan diff the new
    frozen statics against the previous plan's — the exact changed
    static is the recompile cause the next run() will pay for."""
    name = type(wrapper).__name__
    sig = plan_signature(statics)
    prev = getattr(wrapper, "_obs_plan_sig", None)
    record_instant(f"{name}.plan", "plan", wrapper=name, replan=replan)
    if replan and prev is not None:
        changed = diff_statics(prev, sig)
        if changed:
            record_retrace(name, changed, kind="replan")
    wrapper._obs_plan_sig = sig


def top_retrace_causes(snapshot: dict, limit: int = 10) -> List[dict]:
    """Rank the ``plan.retrace_cause`` counter cells:
    ``[{wrapper, key, count}]``, hottest first — the ``obs doctor``
    table that names what keeps retracing."""
    cells = snapshot.get("counters", {}).get("plan.retrace_cause", {})
    rows = []
    for flat, count in cells.items():
        labels = dict(kv.partition("=")[::2] for kv in
                      flat.strip("{}").split(",") if kv)
        rows.append({"wrapper": labels.get("wrapper", "?"),
                     "key": labels.get("key", "?"), "count": int(count)})
    rows.sort(key=lambda r: (-r["count"], r["wrapper"], r["key"]))
    return rows[:limit]


# ---------------------------------------------------------------------------
# request lifecycle (queue / TTFT / TPOT / tokens-per-sec)
# ---------------------------------------------------------------------------


class _Req:
    __slots__ = ("rid", "t_enqueue", "t_begin", "t_first_work",
                 "t_first_token", "t_last_token", "tokens",
                 "prefill_tokens")

    def __init__(self, rid, t_begin, t_enqueue):
        self.rid = rid
        self.t_begin = t_begin
        self.t_enqueue = t_enqueue
        self.t_first_work = None
        self.t_first_token = None
        self.t_last_token = None
        self.tokens = 0
        self.prefill_tokens = 0


_requests: Dict[str, _Req] = {}
_req_lock = threading.Lock()


def _now(now: Optional[float]) -> float:
    return time.perf_counter() if now is None else float(now)


def request_begin(rid: str, *, enqueue_t: Optional[float] = None,
                  now: Optional[float] = None) -> None:
    """Admit request ``rid``.  ``enqueue_t`` (perf_counter seconds) is
    when the request ARRIVED — queue time and TTFT measure from it;
    default: now (no queueing ahead of admission)."""
    t = _now(now)
    with _req_lock:
        _requests[str(rid)] = _Req(str(rid), t,
                                   t if enqueue_t is None
                                   else float(enqueue_t))
    record_instant("request.begin", "request", rid=str(rid))


def prefill_chunk(rid: str, num_tokens: int, *,
                  t0: Optional[float] = None,
                  now: Optional[float] = None) -> None:
    """One prompt chunk of ``num_tokens`` advanced for ``rid``; the
    first chunk closes the queue-time window
    (``lifecycle.queue_us`` = first work - enqueue)."""
    t = _now(now)
    with _req_lock:
        r = _requests.get(str(rid))
        if r is None:
            return
        first = r.t_first_work is None
        if first:
            r.t_first_work = t if t0 is None else float(t0)
            queue_us = (r.t_first_work - r.t_enqueue) * 1e6
        r.prefill_tokens += int(num_tokens)
    if first:
        _reg().observe("lifecycle.queue_us", max(queue_us, 0.0))
    record("request.prefill_chunk", "prefill",
           t if t0 is None else float(t0), t, rid=str(rid),
           num_tokens=int(num_tokens))


def decode_step(rid: str, num_tokens: int = 1, *,
                now: Optional[float] = None) -> None:
    """``num_tokens`` generated for ``rid`` at ``now``.  The first call
    observes TTFT (first token - enqueue); every later call observes
    TPOT as the inter-token gap ``(now - prev) / num_tokens``."""
    t = _now(now)
    ttft_us = tpot_us = queue_us = None
    with _req_lock:
        r = _requests.get(str(rid))
        if r is None:
            return
        if r.t_first_token is None:
            r.t_first_token = t
            ttft_us = (t - r.t_enqueue) * 1e6
            if r.t_first_work is None:
                # decode-only workload: the first token IS the first
                # work, so queue = first token - enqueue (matches the
                # catalog definition and request_finish's summary)
                r.t_first_work = t
                queue_us = max((t - r.t_enqueue) * 1e6, 0.0)
        else:
            tpot_us = (t - r.t_last_token) * 1e6 / max(int(num_tokens), 1)
        r.t_last_token = t
        r.tokens += int(num_tokens)
    reg = _reg()
    if ttft_us is not None:
        reg.observe("lifecycle.ttft_us", max(ttft_us, 0.0))
    if queue_us is not None:
        reg.observe("lifecycle.queue_us", queue_us)
    if tpot_us is not None:
        reg.observe("lifecycle.tpot_us", max(tpot_us, 0.0))
    record_instant("request.decode_step", "decode", t=t, rid=str(rid),
                   num_tokens=int(num_tokens))


def request_finish(rid: str, *, now: Optional[float] = None
                   ) -> Optional[dict]:
    """Close out ``rid``: observes ``lifecycle.tokens_per_s``
    (generated tokens / (finish - enqueue), the whole-request rate) and
    records the request-covering span.  Returns the per-request summary
    (None for an unknown rid)."""
    t = _now(now)
    with _req_lock:
        r = _requests.pop(str(rid), None)
    if r is None:
        return None
    dur = max(t - r.t_enqueue, 1e-9)
    tok_s = r.tokens / dur
    summary = {
        "rid": r.rid,
        "tokens": r.tokens,
        "prefill_tokens": r.prefill_tokens,
        "duration_us": dur * 1e6,
        "queue_us": (None if r.t_first_work is None
                     else (r.t_first_work - r.t_enqueue) * 1e6),
        "ttft_us": (None if r.t_first_token is None
                    else (r.t_first_token - r.t_enqueue) * 1e6),
        "tokens_per_s": tok_s,
    }
    if r.tokens:
        _reg().observe("lifecycle.tokens_per_s", tok_s)
    record("request", "request", r.t_enqueue, t, rid=r.rid,
           tokens=r.tokens, prefill_tokens=r.prefill_tokens,
           ttft_us=summary["ttft_us"])
    return summary


def lifecycle_snapshot() -> Dict[str, dict]:
    """The lifecycle histograms out of the registry snapshot, unflattened
    (``{metric: {count, p50, p99, ...}}``) — what ``examples/
    generate.py`` prints as the per-run summary."""
    snap = _reg().snapshot()
    out: Dict[str, dict] = {}
    for name in ("lifecycle.queue_us", "lifecycle.ttft_us",
                 "lifecycle.tpot_us", "lifecycle.tokens_per_s"):
        cells = snap.get("histograms", {}).get(name)
        if cells:
            # lifecycle histograms carry no labels: one cell
            out[name] = next(iter(cells.values()))
    return out
