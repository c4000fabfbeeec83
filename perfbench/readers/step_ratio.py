"""Reads a ratio of two of the facts ``step()`` returns, each summed over
the window's steps: useful work over launched work (``prefill_units`` /
``prefill_units_launched``), a share of the tokens scheduled, a mean wait
(``queue_wait_s`` / ``first_scheduled``).  Sums first, then the ratio, so a
step counts by its weight.

Parameters: ``num``, ``den`` (names of facts) and optionally ``scale``.
Reads nothing where no step carries both facts, or the denominator is 0."""

from __future__ import annotations


def read(ctx, spec):
    rows = [s for s in ctx["steps"]
            if spec["num"] in s and spec["den"] in s]
    den = sum(s[spec["den"]] for s in rows)
    if not den:
        return None
    num = sum(s[spec["num"]] for s in rows)
    return float(num / den * spec.get("scale", 1))
