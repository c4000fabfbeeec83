"""Reads the harness's own per-step facts: what ``step()`` returned
(``rung``, ``tokens``, ``sampled``, ``finished``) and, in a traced run,
what the harness noted beside it (``live_pages_share``, ``ctx_tokens``).

Parameters: ``field`` (which fact), ``stat`` (mean | median | peak | sum),
over the window's steps that scheduled a token."""

from __future__ import annotations

import statistics

STATS = {"mean": statistics.fmean, "median": statistics.median,
         "peak": max, "sum": sum}


def read(ctx, spec):
    vals = [s[spec["field"]] for s in ctx["steps"]
            if s["tokens"] and spec["field"] in s]
    return float(STATS[spec["stat"]](vals)) if vals else None
