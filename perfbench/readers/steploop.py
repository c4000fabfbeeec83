"""Reads the program's step-loop ledger (``FLASHINFER_TPU_STEPLOOP=1``):
per step the host's sub-phases (``admit``, ``schedule``, ``assemble``,
``lower``, ``dispatch``) and the device window between the dispatch's
return and the sampled tokens reaching the host, both on the host clock.
The harness joins each ledger record to the facts of its step.

Parameters: ``steps`` (``all`` | ``decode``: scheduled no prompt token |
``mixed``: scheduled one), ``value`` (``host_ms`` | ``device_ms`` |
``hbm_share``) and ``stat`` (median | mean).

``hbm_share`` is the least bytes the step must read (the family's
``counts.decode_min_bytes`` of the configuration and the step's facts: the
layers' weights, the head, and the cache rows of the contexts it scheduled)
over the chip's peak bandwidth (``peaks.json``) over the step's device
window, in per cent: a lower bound over a measured time, so it cannot pass
100."""

from __future__ import annotations

import statistics

PHASES = ("admit", "schedule", "assemble", "lower", "dispatch")


def _value(s, what, ctx):
    rec = s["ledger"]
    if what == "host_ms":
        return sum(rec["phases"].get(p, 0.0) for p in PHASES) * 1e3
    if rec["device_us"] is None:
        return None
    if what == "device_ms":
        return rec["device_us"] / 1e3
    if what == "hbm_share":
        if ctx["peaks"] is None or "ctx_tokens" not in s:
            return None
        least = ctx["family"].counts.decode_min_bytes(ctx["model"], s) \
            / ctx["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least / (rec["device_us"] / 1e6)
    raise ValueError(f"unknown value {what!r}")


def read(ctx, spec):
    keep = {"all": lambda s: True,
            "decode": lambda s: s["tokens"] == s["sampled"],
            "mixed": lambda s: s["tokens"] > s["sampled"]}[spec["steps"]]
    vals = [_value(s, spec["value"], ctx) for s in ctx["steps"]
            if "ledger" in s and keep(s)]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    stat = statistics.median if spec["stat"] == "median" else statistics.fmean
    return float(stat(vals))
