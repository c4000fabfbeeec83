"""Arithmetic from a window's request timeline to the end-to-end metrics.

Everything here is pure Python over host-clock stamps (seconds of
``time.perf_counter``), so it has unit tests on hand-made timelines
(``tests/test_metrics.py``).  A request record is a dict:

    t_submit     when the harness handed it to the engine
    token_times  the stamp after the step that produced each output token
    asked        output tokens asked for
    t_end        stamp of the step that finished it (None while in flight)
    failed       the engine refused it, it ended short, or a token was
                 outside the vocabulary

The window is ``(t_open, t_close]``: a token, a first token or an end
stamped exactly at ``t_open`` belongs to the time before it.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """Nearest-rank percentile: the smallest value with at least ``q`` per
    cent of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def in_window(t, t_open, t_close):
    return t_open < t <= t_close


def ttfts_ms(requests, t_open, t_close):
    """Submit -> first token, of the requests whose FIRST token fell in the
    window (whenever they were submitted)."""
    return [(r["token_times"][0] - r["t_submit"]) * 1e3 for r in requests
            if r["token_times"]
            and in_window(r["token_times"][0], t_open, t_close)]


def gaps_ms(requests, t_open, t_close):
    """Gaps between consecutive output tokens of one request, of every
    gap whose LATER token fell in the window."""
    out = []
    for r in requests:
        tt = r["token_times"]
        out.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:])
                   if in_window(b, t_open, t_close))
    return out


def tokens_in_window(requests, t_open, t_close):
    return sum(in_window(t, t_open, t_close)
               for r in requests for t in r["token_times"])


def ended_in_window(requests, t_open, t_close):
    return [r for r in requests
            if r["t_end"] is not None
            and in_window(r["t_end"], t_open, t_close)]


def end_to_end(requests, t_open, t_close):
    """{metric name: (value, unit)} plus the sample counts beside them.

    A metric whose sample is empty is left out: the harness then fails the
    run, because every cell has to report every end-to-end metric."""
    span = t_close - t_open
    if span <= 0:
        raise ValueError("empty window")
    ttft = ttfts_ms(requests, t_open, t_close)
    gaps = gaps_ms(requests, t_open, t_close)
    toks = tokens_in_window(requests, t_open, t_close)
    ended = ended_in_window(requests, t_open, t_close)
    out = {"out_tokens_per_s": (toks / span, "tokens/s")}
    if ttft:
        out["ttft_p50_ms"] = (statistics.median(ttft), "ms")
        out["ttft_p95_ms"] = (percentile(ttft, 95), "ms")
        out["ttft_mean_ms"] = (statistics.fmean(ttft), "ms")
    if gaps:
        out["itl_p95_ms"] = (percentile(gaps, 95), "ms")
        out["itl_p50_ms"] = (statistics.median(gaps), "ms")
    counts = {"window_s": span, "tokens": toks, "ttft_samples": len(ttft),
              "gap_samples": len(gaps), "attempted": len(ended),
              "failed": sum(bool(r["failed"]) for r in ended)}
    return out, counts
