"""Reads the reduction of the profiler's device trace
(``trace_reduce.reduce``).

Parameters: ``value``: ``idle_share`` (100 x (1 - busy / window)) or
``op_ms_per_step`` (self time of the device operations whose name matches
one of ``patterns``, regular expressions, summed and divided by the steps
traced)."""

from __future__ import annotations

import re


def op_seconds(ctx, patterns):
    """Self seconds, over the traced steps, of the device operations whose
    name matches one of ``patterns``; None where the trace has none."""
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    pats = [re.compile(p) for p in patterns]
    hit = [s for n, s in t["op_s"].items() if any(p.search(n) for p in pats)]
    return sum(hit) if hit else None


def read(ctx, spec):
    t = ctx["trace"]
    if not t:
        return None
    if spec["value"] == "idle_share":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if spec["value"] == "op_ms_per_step":
        secs = op_seconds(ctx, spec["patterns"])
        return None if secs is None else 1e3 * secs / t["steps"]
    raise ValueError(f"unknown value {spec['value']!r}")
