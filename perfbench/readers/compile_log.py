"""Reads the program's compile log (``flashinfer_tpu.obs.steploop
compile_log()``, ``FLASHINFER_TPU_STEPLOOP=1``): one line for each engine
step that traced or during which JAX's compiler events fired, with the
seconds ``jax.monitoring`` gave for tracing (``compile.trace``), lowering
(``compile.lower``), the backend's compile or its load from the persistent
cache (``compile.backend``), and the step's ``fetch``, the program's first
run.  The log outlives the ``steploop.reset()`` the harness makes before
its window.

Parameter: ``field``, the key to add up over the lines of SET-UP: those
whose engine step index lies before the window's first step.  In seconds.
A program without the log (or a run with no such line) reads nothing."""

from __future__ import annotations

import sys


def read(ctx, spec):
    steploop = sys.modules.get("flashinfer_tpu.obs.steploop")
    log = getattr(steploop, "compile_log", None)
    first = next((s["step"] for s in ctx["steps"] if "step" in s), None)
    if log is None or first is None:
        return None
    vals = [ln[spec["field"]] for ln in log()
            if ln["surface"] == "ServingEngine" and ln["step"] < first]
    return float(sum(vals)) if vals else None
