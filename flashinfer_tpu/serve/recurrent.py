"""The step's plan for a model that brings its own block
(``serve/engine.py``, "The model's seam"): how many of the rung's rows are
tokens and, for a model with recurrent or latent layers, where each batch
slot's segment lies in the flat batch and the work units of the longer
segments (a recurrent layer's scan, a latent layer's sparse attention: both
run one unit's rows of one request at a time).

A recurrent layer keeps a fixed-size state per running request in the
request's SLOT (its batch slot): the engine's state arrays are
``[max_batch, ...]``.  One step's flat batch holds, per scheduled request,
one contiguous SEGMENT of ``n`` tokens: ``n == 1`` for a decoding lane (and
for a one-token prompt chunk), up to the prefill budget for a prompt chunk.
The layer has to run its recurrence per segment, from the slot's state (or
from zero, when the request was (re)admitted since its last step) and write
the slot back.  What the compiled step needs of that, in shapes fixed by the
rung:

``n_tokens``  int32 scalar: rows beyond it are padding.
``seg``       int32 ``[max_batch, 3]``: per slot ``(row0, n, fresh)``; ``n``
              is 0 for a slot with nothing scheduled.  One-token segments
              are advanced from this table alone, all slots at once.
``work``      int32 ``[units, 6]``: the segments of two tokens or more cut
              into units of at most ``chunk`` rows, in order: ``(row0, n,
              slot, first, last, fresh)``.  ``first`` loads the slot's state
              (zero when ``fresh``), ``last`` writes it back; between them
              the state is carried from unit to unit.
``n_work``    int32 scalar: how many units are real (the loop's bound).

Imported only when a model asks for it.
"""

from __future__ import annotations

import numpy as np


def work_units(rung: int, max_batch: int, chunk: int) -> int:
    """The most units a step of ``rung`` rows can need: every segment adds
    at most one partial unit to ``rung / chunk`` full ones."""
    return rung // min(chunk, rung) + max_batch


def build_plan(rsegs, *, tokens: int, rung: int, max_batch: int, chunk: int):
    """(plan arrays, facts) of one step.  ``rsegs``: ``(row0, n, slot,
    fresh)`` per scheduled request; ``chunk`` 0: no recurrent layer."""
    plan = {"n_tokens": np.int32(tokens)}
    if not chunk:
        return plan, {}
    q = min(chunk, rung)
    seg = np.zeros((max_batch, 3), np.int32)
    work = np.zeros((work_units(rung, max_batch, chunk), 6), np.int32)
    w = lanes = long_tokens = 0
    for row0, n, slot, fresh in rsegs:
        seg[slot] = (row0, n, fresh)
        if n == 1:
            lanes += 1
            continue
        long_tokens += n
        for off in range(0, n, q):
            work[w] = (row0 + off, min(q, n - off), slot, off == 0,
                       off + q >= n, fresh)
            w += 1
    plan.update(seg=seg, work=work, n_work=np.int32(w))
    return plan, {"ssm_prefill_tokens": long_tokens,
                  "ssm_decode_lanes": lanes}
