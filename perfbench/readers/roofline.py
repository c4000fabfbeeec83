"""Reads a share of the roofline: the least time the chip could take for
the work of the traced steps, over the device time the selected operations
took in those same steps, in per cent.

The harness marks which of the window's steps the profiler recorded
(``ctx["trace"]["step_indices"]``), so their facts in ``ctx["steps"]``
(``tokens``, ``sampled``, ``prefill_tokens``, ``ctx_tokens``) sit beside
their device time.  The least time of one step is the largest of its
``bounds``: each names a ``count``, a function ``(config, facts)`` of the
family's counts (``ctx["family"].counts``), and the ``peak`` of
``peaks.json`` it is divided by (operations over ``bf16_flops_per_s``, bytes
over ``hbm_bytes_per_s``).  A count is the LEAST the algorithm needs, so the
share is a lower bound over a measured time and stays under 100; a reading
over 100 is a fault of the count or of the selection, and nothing here
clips it.

Parameters: ``bounds`` ([{``count``, ``peak``}, ...]) and either ``scopes``
(with ``exclude``), which select operations as ``xplane_scopes`` does, or
``patterns``, as ``xplane`` does.  Reads nothing where the traced steps,
their facts, the peaks or the operations are missing.  Says, on a
``[perfbench]`` line, which steps and token counts it was computed from and
which bound held in each."""

from __future__ import annotations

from readers import xplane, xplane_scopes


def read(ctx, spec):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not t.get("step_indices") or peaks is None:
        return None
    busy = xplane_scopes.seconds(ctx, spec) if "scopes" in spec \
        else xplane.op_seconds(ctx, spec["patterns"])
    if not busy:
        return None
    bounds = [(b["count"], getattr(ctx["family"].counts, b["count"]),
               peaks[b["peak"]]) for b in spec["bounds"]]
    least, told = 0.0, []
    for i in t["step_indices"]:
        facts = ctx["steps"][i]
        try:
            times = {name: count(ctx["model"], facts) / peak
                     for name, count, peak in bounds}
        except KeyError:      # a fact the count needs is not in this step
            return None
        held = max(times, key=times.get)
        least += times[held]
        told.append(f"step {i}: {facts['tokens']} tokens "
                    f"({facts.get('prefill_tokens', '?')} prefill), {held} "
                    f"{1e3 * times[held]:.3f} ms")
    ctx["say"](f"roofline over {1e3 * busy:.3f} ms of "
               f"{spec.get('scopes') or spec['patterns']}: "
               + "; ".join(told) + f"; least {1e3 * least:.3f} ms")
    return 100.0 * least / busy
