"""Process-wide metrics registry: counters, gauges, fixed-bucket histograms.

TPU re-design of the reference's observability layer (device event
buffers -> Perfetto in ``include/flashinfer/profiler.cuh:33-80``, leveled
``@flashinfer_api`` logging): the *metrics* half.  Where the profiler
answers "what ran when", this registry answers "how often / how long /
how wasteful" across a process lifetime, cheap enough to leave wired
into the hot paths.

Design constraints (ISSUE 2 tentpole):

- **No-op-cheap when disabled.**  ``FLASHINFER_TPU_METRICS=0`` (the
  default) must cost instrumented call sites one function call + one
  env-dict lookup; the ``@flashinfer_api`` fast path additionally folds
  the check into its single instrumentation-active branch
  (api_logging.py).  The gate lives in the ``flashinfer_tpu.obs``
  facade; the registry itself is ALWAYS functional, so infrastructure
  that has already paid for the slow path (the api-log call index, the
  bench auditor) can count unconditionally.
- **Thread-safe when on.**  One lock per registry around every mutation
  and snapshot — serving loops call decorated ops from executor threads
  (the same reason trace.py takes a lock for its jsonl writes).
- **Fixed buckets, derived quantiles.**  Histograms use immutable
  bucket boundaries fixed at first observation (log-spaced defaults
  suited to host-dispatch latencies); p50/p90/p99 are interpolated from
  bucket counts at snapshot time, so ``observe()`` is O(len(buckets))
  bisection with no sample retention.

Metric names and label schemas are declared in ``obs.catalog`` — the
analysis pass L005 cross-checks the public-API surface against it.
"""

from __future__ import annotations

import bisect
import os
import threading
from typing import Dict, Iterable, Optional, Tuple

# log-spaced µs boundaries covering sub-µs host bookkeeping up to the
# multi-second first-compile outliers
DEFAULT_BUCKETS_US: Tuple[float, ...] = (
    1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1e3, 2e3, 5e3, 1e4, 2e4, 5e4, 1e5, 2e5, 5e5, 1e6, 5e6,
)

# percentage-valued histograms (padding waste): linear buckets
PERCENT_BUCKETS: Tuple[float, ...] = (
    0.0, 1.0, 2.0, 5.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0,
    90.0, 95.0, 100.0,
)

LabelKey = Tuple[Tuple[str, str], ...]


def metrics_enabled() -> bool:
    """The ``FLASHINFER_TPU_METRICS`` gate (default off), read lazily per
    call like every other ``FLASHINFER_TPU_*`` flag so tests can
    monkeypatch it (env.py module docstring)."""
    return os.environ.get("FLASHINFER_TPU_METRICS", "0") not in ("", "0")


def spans_enabled() -> bool:
    """The ``FLASHINFER_TPU_SPANS`` gate (default off) for the serving
    flight recorder (obs.spans).  Lives HERE, not in spans.py, so the
    gate check never imports the spans machinery — with the flag unset,
    plain library use must not load obs.spans at all (the subprocess
    pin in tests/test_obs_spans.py, the costmodel precedent)."""
    return os.environ.get("FLASHINFER_TPU_SPANS", "0") not in ("", "0")


def steploop_enabled() -> bool:
    """The ``FLASHINFER_TPU_STEPLOOP`` gate (default off) for the
    step-loop flight deck (obs.steploop): per-step host/device overlap
    ledger, its phases also profiler spans.  Same placement rule as
    :func:`spans_enabled` — the gate lives HERE so checking it never
    imports the steploop machinery (the zero-overhead subprocess pin in
    tests/test_steploop.py).  Gate-ON steps pay a completion probe
    (device sync per step), so this is a measurement mode, never a
    production default."""
    return os.environ.get("FLASHINFER_TPU_STEPLOOP", "0") not in ("", "0")


def _label_key(labels: Dict[str, object]) -> LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class Histogram:
    """Fixed-boundary histogram with interpolated quantiles.

    Not self-locking: the owning :class:`Registry` serializes access
    (one registry lock beats one lock per metric cell for snapshot
    consistency).
    """

    __slots__ = ("boundaries", "counts", "count", "total", "vmin", "vmax")

    def __init__(self, boundaries: Iterable[float]):
        self.boundaries = tuple(float(b) for b in boundaries)
        if list(self.boundaries) != sorted(set(self.boundaries)):
            raise ValueError("histogram boundaries must be sorted, unique")
        # counts[i] covers (boundaries[i-1], boundaries[i]]; the final
        # slot is the +Inf overflow bucket
        self.counts = [0] * (len(self.boundaries) + 1)
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def observe(self, value: float) -> None:
        value = float(value)
        self.counts[bisect.bisect_left(self.boundaries, value)] += 1
        self.count += 1
        self.total += value
        self.vmin = min(self.vmin, value)
        self.vmax = max(self.vmax, value)

    def quantile(self, q: float) -> Optional[float]:
        """Linear interpolation within the bucket holding rank q*count
        (Prometheus histogram_quantile semantics), clamped to the
        observed [min, max] so tiny samples don't report a bucket edge
        far beyond any real observation."""
        if self.count == 0:
            return None
        rank = q * self.count
        acc = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if acc + c >= rank:
                lo = self.boundaries[i - 1] if i > 0 else 0.0
                hi = (self.boundaries[i] if i < len(self.boundaries)
                      else self.vmax)
                frac = (rank - acc) / c
                est = lo + (hi - lo) * frac
                return max(self.vmin, min(est, self.vmax))
            acc += c
        return self.vmax

    def to_dict(self) -> dict:
        d = {
            "count": self.count,
            "sum": self.total,
        }
        if self.count:
            d.update(
                min=self.vmin, max=self.vmax,
                p50=self.quantile(0.50), p90=self.quantile(0.90),
                p99=self.quantile(0.99),
                buckets={
                    ("+Inf" if i == len(self.boundaries)
                     else repr(self.boundaries[i])): c
                    for i, c in enumerate(self.counts) if c
                },
            )
        return d


class Registry:
    """Thread-safe metric store.  Cells are created on first touch; a
    metric name maps to a dict of label-sets so ``snapshot()`` can emit
    the Prometheus-style grouped form."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Dict[LabelKey, int]] = {}
        self._gauges: Dict[str, Dict[LabelKey, float]] = {}
        self._hists: Dict[str, Dict[LabelKey, Histogram]] = {}
        self._hist_buckets: Dict[str, Tuple[float, ...]] = {}

    # -- mutation ---------------------------------------------------------

    def counter_inc(self, name: str, value: int = 1, **labels) -> int:
        key = _label_key(labels)
        with self._lock:
            cells = self._counters.setdefault(name, {})
            cells[key] = new = cells.get(key, 0) + int(value)
        return new

    def gauge_set(self, name: str, value: float, **labels) -> None:
        with self._lock:
            self._gauges.setdefault(name, {})[_label_key(labels)] = \
                float(value)

    def observe(self, name: str, value: float,
                buckets: Optional[Iterable[float]] = None, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            cells = self._hists.setdefault(name, {})
            h = cells.get(key)
            if h is None:
                bounds = (tuple(buckets) if buckets is not None
                          else self._hist_buckets.get(name,
                                                      DEFAULT_BUCKETS_US))
                h = cells[key] = Histogram(bounds)
            h.observe(value)

    def declare_histogram(self, name: str,
                          buckets: Iterable[float]) -> None:
        """Pin bucket boundaries for `name` ahead of the first observe
        (the catalog declares percent-valued histograms this way)."""
        with self._lock:
            self._hist_buckets[name] = tuple(buckets)

    # -- read side --------------------------------------------------------

    def snapshot(self) -> dict:
        """One JSON-ready dict of everything recorded so far.  Label
        sets render as ``name{k=v,...}`` flat keys — trivially diffable
        and greppable, and the exporters re-parse them losslessly."""

        def flat(cells, render):
            out = {}
            for key, val in sorted(cells.items()):
                lbl = ",".join(f"{k}={v}" for k, v in key)
                out["{" + lbl + "}" if lbl else ""] = render(val)
            return out

        with self._lock:
            return {
                "counters": {
                    name: flat(cells, int)
                    for name, cells in sorted(self._counters.items())
                },
                "gauges": {
                    name: flat(cells, float)
                    for name, cells in sorted(self._gauges.items())
                },
                "histograms": {
                    name: flat(cells, Histogram.to_dict)
                    for name, cells in sorted(self._hists.items())
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


_global = Registry()


def get() -> Registry:
    return _global
