"""Reads a value the harness's own window arithmetic (``metrics.py``)
already has, for a quantity that stands among the per-layer metrics and not
among the end-to-end ones.  Parameter: ``value``, its name there."""

from __future__ import annotations


def read(ctx, spec):
    v = ctx["values"].get(spec["value"])
    return None if v is None else float(v[0])
