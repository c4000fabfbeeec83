"""Kernel-level profiling.

TPU re-design of the reference profiler (``include/flashinfer/
profiler.cuh:33-80`` device event buffer -> Perfetto,
``flashinfer/profiler/__init__.py:33-95``): on TPU the runtime already
emits a full device-side timeline — ``jax.profiler`` captures XLA/Mosaic
kernel spans to a Perfetto/TensorBoard trace, so the in-kernel tag
machinery collapses into this context manager plus named annotations.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def kernel_profiler(log_dir: str = "/tmp/flashinfer_tpu_trace") -> Iterator[str]:
    """Capture a device trace for the enclosed region.

    View with Perfetto (ui.perfetto.dev) or TensorBoard's profile plugin —
    the analogue of the reference's Perfetto export (profiler/__init__.py).
    """
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named span in the device trace (reference profiler event tags)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def scope(name: str) -> Iterator[None]:
    """Named span usable INSIDE jit-traced code: the emitted XLA ops
    carry ``name`` so device traces attribute work to serving phases.
    ``annotate`` is the host-side twin (TraceAnnotation does nothing
    under tracing)."""
    import jax

    with jax.named_scope(name):
        yield


# ---------------------------------------------------------------------------
# Op-level event timeline (reference profiler.cuh event-buffer analogue):
# every @flashinfer_api call between start_timeline()/stop_timeline() is
# recorded and exportable as chrome://tracing JSON.  Host-side spans by
# default (dispatch cost); set FLASHINFER_TPU_TIMELINE_SYNC=1 to
# block_until_ready each op for true wall durations.
#
# Thread-safe (ISSUE 2 satellite): serving loops drive decorated ops
# from executor threads, and the previous bare-global-list design could
# lose events (append after a concurrent stop) or double-export (two
# concurrent stops returning the same list).  Same pattern trace.py
# already uses for its jsonl writes: one module lock around every
# mutation; timeline_active() stays lock-free (a benign race — the
# recorder re-checks under the lock).
# ---------------------------------------------------------------------------

import threading as _threading
import time as _time

_timeline_lock = _threading.Lock()
_timeline_events: Optional[list] = None

# ---- the shared clock base (ISSUE 10 satellite) ----------------------------
# Every host-side recorder in the tree (this op timeline, obs.spans'
# flight recorder) stamps time.perf_counter() values; chrome-trace
# exports used to write those RAW (perf_counter epoch ~= process start)
# while other tooling wrote wall-clock — two trace files whose
# timelines could never merge.  One anchor, captured once at import,
# converts every perf_counter stamp to a common epoch-based µs value,
# so `obs trace` nests spans and op events on ONE timeline.  (The two
# clocks drift only by NTP slew after import — harmless at trace
# scale; what matters is that every exporter uses the SAME anchor.)
_EPOCH_ANCHOR = _time.time() - _time.perf_counter()


def epoch_anchor() -> float:
    """Epoch seconds at ``time.perf_counter() == 0`` (this process)."""
    return _EPOCH_ANCHOR


def perf_to_epoch_us(t: float) -> float:
    """A ``time.perf_counter`` stamp -> epoch-based microseconds on the
    shared trace timeline."""
    return (float(t) + _EPOCH_ANCHOR) * 1e6


def timeline_active() -> bool:
    return _timeline_events is not None


def start_timeline() -> None:
    global _timeline_events
    with _timeline_lock:
        _timeline_events = []


def record_event(name: str, t0: float, t1: float) -> None:
    with _timeline_lock:
        if _timeline_events is not None:
            _timeline_events.append({"name": name, "ts": t0, "dur": t1 - t0})


def stop_timeline(path: Optional[str] = None) -> list:
    """Stop recording; return the events and optionally write a
    chrome://tracing / Perfetto-loadable JSON file.  Concurrent-stop
    safe: the event list is swapped out under the lock, so exactly one
    caller gets the events — a second stop returns []."""
    global _timeline_events
    with _timeline_lock:
        events = _timeline_events or []
        _timeline_events = None
    if path is not None:
        import json
        import os

        trace = {
            "traceEvents": [
                {
                    "name": e["name"], "ph": "X", "pid": os.getpid(), "tid": 0,
                    # epoch-based µs via the shared anchor, so this file
                    # and the obs.export traces share one clock base
                    "ts": perf_to_epoch_us(e["ts"]), "dur": e["dur"] * 1e6,
                    "cat": "flashinfer_tpu",
                }
                for e in events
            ]
        }
        from flashinfer_tpu.utils import atomic_write_text

        atomic_write_text(path, json.dumps(trace))
    return events


@contextlib.contextmanager
def timeline(path: Optional[str] = None) -> Iterator[None]:
    """``with timeline("trace.json"):`` — record every flashinfer_tpu API
    call in the region to a chrome://tracing file."""
    start_timeline()
    try:
        yield
    finally:
        stop_timeline(path)


# ---------------------------------------------------------------------------
# Reference in-kernel profiler surface (flashinfer/profiler/__init__.py:28-
# 120: device tag buffer -> decode_tag -> perfetto export).  TPU re-design:
# Mosaic exposes no in-kernel clock, but the TPU grid executes
# SEQUENTIALLY per core, so an ordered tag stream fully determines the
# schedule; timestamps are synthesized from stream order.  Real wall-time
# kernel profiles come from jax.profiler (Mosaic regions are visible
# there) and the op timeline above; this surface decodes/export-formats
# tag buffers in the reference's layout so tooling ports unchanged.
# ---------------------------------------------------------------------------

import enum as _enum


class EventType(_enum.Enum):
    kBegin = 0
    kEnd = 1
    kInstant = 2


def decode_tag(tag: int, num_blocks: int, num_groups: int):
    """Decode a profiler tag (reference bit layout — bits 0-1 event_type,
    2-11 event_idx, 12-23 block_group_idx, 24-31 sm_id; on TPU the
    "sm_id" field carries the core index, 0 on single-core chips)."""
    sm_id = (tag >> 24) & 0xFF
    block_group_idx = (tag >> 12) & 0xFFF
    event_idx = (tag >> 2) & 0x3FF
    event_type = tag & 0x3
    return (
        block_group_idx // num_groups,
        block_group_idx % num_groups,
        event_idx,
        event_type,
        sm_id,
    )


def encode_tag(block_idx: int, group_idx: int, num_groups: int,
               event_idx: int, event_type: EventType,
               sm_id: int = 0) -> int:
    """Inverse of :func:`decode_tag` — kernels (or host-side recorders)
    build tags with it."""
    bg = block_idx * num_groups + group_idx
    return (
        (int(sm_id) & 0xFF) << 24
        | (bg & 0xFFF) << 12
        | (int(event_idx) & 0x3FF) << 2
        | int(
            event_type.value if isinstance(event_type, EventType)
            else event_type
        )
    )


def export_to_perfetto_trace(profiler_buffer, event_names, file_name):
    """Export a tag buffer to a chrome-trace JSON that Perfetto opens
    directly (reference export_to_perfetto_trace; tg4perfetto protobuf
    replaced with the dependency-free JSON form).

    ``profiler_buffer``: int/uint array — element 0 packs
    (num_blocks, num_groups) as two uint16-in-int32 fields like the
    reference's header; subsequent NONZERO elements are either packed
    ``(tag << 32) | timestamp`` uint64s (reference layout) or plain tags
    (TPU sequential-grid form — timestamps synthesized from order)."""
    import json as _json

    import numpy as _np

    buf = _np.asarray(profiler_buffer).reshape(-1)
    header = int(buf[0])
    num_blocks = max(header & 0xFFFF, 1)
    num_groups = max((header >> 16) & 0xFFFF, 1)
    events = []
    seq = 0
    for raw in buf[1:]:
        raw = int(raw)
        if raw == 0:
            continue
        if raw > 0xFFFFFFFF:  # packed (tag, timestamp)
            tag, ts = raw >> 32, raw & 0xFFFFFFFF
        else:
            tag, ts = raw, seq
            seq += 1
        blk, grp, ev, et, sm = decode_tag(tag, num_blocks, num_groups)
        name = (
            event_names[ev] if ev < len(event_names) else f"event_{ev}"
        )
        ph = {0: "B", 1: "E", 2: "i"}[et & 0x3]
        events.append({
            "name": name, "ph": ph, "ts": ts,
            "pid": sm, "tid": blk * num_groups + grp,
            **({"s": "t"} if ph == "i" else {}),
        })
    with open(file_name, "w") as fh:
        _json.dump({"traceEvents": events}, fh)


def grid_trace_to_buffer(tags) -> "object":
    """Pack a kernel's per-grid-step tag array (e.g.
    ``fused_paged_prefill(..., trace_events=True)``'s ``[Hkv, units]``)
    into the reference profiler-buffer layout consumable by
    :func:`export_to_perfetto_trace`: element 0 = header
    (num_blocks | num_groups << 16), then the tags in grid order."""
    import numpy as _np

    tags = _np.asarray(tags)
    num_blocks = tags.shape[-1]
    if num_blocks > 0xFFFF:
        raise ValueError(f"{num_blocks} blocks exceed the 16-bit header")
    # the kernel encodes the unit straight into the block_group field
    # (group = 0; the head rides sm_id), so the header declares
    # num_groups = 1 — consumers decoding with header fields then get
    # blk == unit exactly
    header = num_blocks | (1 << 16)
    return _np.concatenate(
        [_np.array([header], _np.int64), tags.reshape(-1).astype(_np.int64)]
    )


class TraceGenerator:
    """Reference profiler.TraceGenerator: accumulates profiler events and
    emits a trace file.  Wraps this module's timeline recorder."""

    def __init__(self, path: str = "/tmp/flashinfer_tpu_timeline.json"):
        self.path = path
        start_timeline()

    def record(self, name: str, t0: float, t1: float) -> None:
        record_event(name, t0, t1)

    def flush(self):
        return stop_timeline(self.path)
