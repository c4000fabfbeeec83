"""Reads named phases of the program's step-loop ledger
(``FLASHINFER_TPU_STEPLOOP=1``), which are also the ``engine.<phase>``
spans of the profiler's host plane: the five phases of the host window
(``admit``, ``schedule``, ``assemble``, ``lower``, ``dispatch``) in a
record's ``phases``, and in its ``spans`` their dotted children
(``lower.build``, ``lower.upload``, ``dispatch.upload``, ``dispatch.call``)
and the phases after the dispatch (``fetch``, ``post``).

Parameters: ``phases`` (the names to add up per step) and ``stat`` (median |
mean, over the window's steps).  A step whose record lacks one of the names
is left out, so a program that does not write them reads nothing."""

from __future__ import annotations

import statistics


def read(ctx, spec):
    vals = []
    for s in ctx["steps"]:
        rec = s.get("ledger")
        if rec is None:
            continue
        have = {**rec["phases"], **rec.get("spans", {})}
        if all(p in have for p in spec["phases"]):
            vals.append(sum(have[p] for p in spec["phases"]) * 1e3)
    if not vals:
        return None
    stat = statistics.median if spec["stat"] == "median" else statistics.fmean
    return float(stat(vals))
