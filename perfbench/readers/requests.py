"""Reads the window's request records (fields the harness copied from the
engine's request objects: ``hit_tokens``, ``preemptions``; and its own:
``prompt``, ``asked``).

Parameters: ``over`` (``admitted``: submitted inside the window; ``ended``:
ended inside it), ``sum`` (the field summed), and optionally ``per`` (a
field, or ``prompt_tokens``, to divide by) and ``scale``."""

from __future__ import annotations


def _field(r, name):
    return len(r["prompt"]) if name == "prompt_tokens" else r[name]


def read(ctx, spec):
    rows = ctx["admitted"] if spec["over"] == "admitted" else ctx["requests"]
    if not rows:
        return None
    num = sum(_field(r, spec["sum"]) for r in rows)
    if "per" in spec:
        den = sum(_field(r, spec["per"]) for r in rows)
        if not den:
            return None
        num /= den
    return float(num * spec.get("scale", 1))
