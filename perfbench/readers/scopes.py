"""Reads the device time of named parts of the step program, the parts
being named in the metric's own file, and optionally their share of a
roofline.

``xplane_scopes`` knows the eight parts of the Llama-style step by a fixed
list, and an operation under any other ``jax.named_scope`` reads
``unscoped`` there.  A model that brings its own block brings its own parts
(``ssm.in_proj``, ``ssm.conv``, ``ssm.scan``, ``ssm.out``, ``moe.route``,
``moe.experts``, ``moe.shared``), so this reader takes the names from
``spec["scopes"]``: an event belongs to the LAST component of its
``op_name`` that is one of those names or of ``xplane_scopes.SCOPES``
(so a kernel nested in ``attn.core`` inside another part stays with
``attn.core``).  The recording, the join of events with paths and the
self-time rule are ``xplane_scopes``'s and ``trace_reduce``'s.

Parameters: ``scopes`` (the parts to add up) and optionally ``exclude``
(regular expressions on the operation's name).  ``unscoped`` among the
``scopes`` is the remainder: the operations under none of ``xplane_scopes``'s
parts, of the parts added up, or of the further ones listed under ``known``
(the block's other parts), so that a cell's parts and its remainder add up to
the device's busy time.  With ``bounds`` (``[{count,
peak}, ...]``, as ``roofline`` takes them) the value is the traced steps'
least time by the family's counts over the parts' device time, in per cent,
clipped nowhere; without, the parts' milliseconds per traced step.  Reads
nothing where the trace has no operation under any of the named or ``known``
parts (a program that lacks them), or, for a share, where a count, a fact or the
peaks are missing.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import trace_reduce as tr
from readers import xplane_scopes as xs


@functools.lru_cache(maxsize=2)
def _events(path, _mtime):
    return xs.load(path)


def _scope_of(path, known):
    for part in reversed((path or "").split("/")):
        if part in known:
            return part
    return "unscoped"


def seconds(ctx, spec):
    """Self seconds, over the traced steps, of the operations under
    ``spec["scopes"]``; None where there is none."""
    t = ctx["trace"]
    found = glob.glob(str(xs.TRACE_DIR / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not t or not t["steps"] or not found:
        return None
    dev, spans = _events(found[0], os.path.getmtime(found[0]))
    wanted = set(spec["scopes"])
    own = (wanted | set(spec.get("known", ()))) - {"unscoped"}
    known = own | set(xs.SCOPES)
    pats = [re.compile(p) for p in spec.get("exclude", ())]
    lo = spans[0][0] if spans else float("-inf")
    hi = spans[-1][1] if spans else float("inf")
    total, seen = 0.0, False
    for evs in dev:
        labelled = []
        for name, start, dur, path in evs:
            if start + dur <= lo or start >= hi:
                continue
            scope = _scope_of(path, known)
            if any(p.search(tr.op_name(name)) for p in pats):
                scope = None
            seen |= scope in own
            labelled.append((scope in wanted, start, dur))
        total += tr.self_times(labelled).get(True, 0.0)
    return total if seen else None


def read(ctx, spec):
    busy = seconds(ctx, spec)
    if not busy:
        return None
    t = ctx["trace"]
    if "bounds" not in spec:
        return 1e3 * busy / t["steps"]
    if not t.get("step_indices") or ctx["peaks"] is None:
        return None
    least, told = 0.0, []
    for i in t["step_indices"]:
        facts = ctx["steps"][i]
        try:
            times = {b["count"]: getattr(ctx["family"].counts, b["count"])(
                ctx["model"], facts) / ctx["peaks"][b["peak"]]
                for b in spec["bounds"]}
        except (AttributeError, KeyError):   # a count or a fact is not there
            return None
        held = max(times, key=times.get)
        least += times[held]
        told.append(f"step {i}: {facts['tokens']} tokens, {held} "
                    f"{1e3 * times[held]:.3f} ms")
    ctx["say"](f"roofline over {1e3 * busy:.3f} ms of {spec['scopes']}: "
               + "; ".join(told) + f"; least {1e3 * least:.3f} ms")
    return 100.0 * least / busy
