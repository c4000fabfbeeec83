"""The K/V pages of a model's WINDOW layers: a second pool, sized by the
window, beside the pages of its full layers.

A layer whose queries see only the last ``attention_window`` positions
(the model's ``layer_kinds()`` says ``"window"``) needs, of a request ``n``
tokens long, the rows of positions ``n - attention_window`` on; a full layer
needs all of them.  Kept in the engine's one pool a window layer would hold
every page a full layer holds (at a 16k context, eight times what it reads).
So the engine gives the window layers' K/V arrays ``[window_pages, Hkv,
page_size, D]`` a :class:`BlockPool` of their own, whose size follows from
the window, the largest chunk a step gives one request and ``max_batch``
(no knob), and a request a second page list in it:

- ``EngineRequest.wpages`` are the pages of page indices ``wfirst ..
  wfirst + len(wpages) - 1`` of the request (page index = position //
  page_size): a COMPACT list, the released pages gone from its front;
- before a step, :meth:`WindowPages.assemble` extends every scheduled
  request's list to the last position the step writes;
- after it, :meth:`WindowPages.trim` releases the pages that lie wholly
  behind ``kv_len - attention_window``, so a request never holds more than
  :func:`pages_cap` pages;
- admission reserves a request's most (:meth:`WindowPages.reserve`), release
  and preemption free pages and reservation (:meth:`WindowPages.release`).

Inside the step the window layers count positions from the first page a
request still holds (``first_tok``): the causal and the window rule read
differences of positions only, so the plan of the window layers
(``engine_kernels.build_engine_work_units`` with ``window_left``) and the
reference tier's windows are those of a shorter request.  Rotation uses the
global positions.

What cannot be honoured once pages are released is refused by the engine by
name (``_refuse_own_state``): prefix lookups, host spill, migration,
``offload_idle``, int8 pages.  Preemption is recompute.

Imported only for a model with window layers.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from flashinfer_tpu.serve.engine import BlockPool


def pages_cap(window: int, chunk: int, page_size: int) -> int:
    """The most window pages one request holds: during a step of ``chunk``
    rows its pages span positions ``kv_len - window .. kv_len + chunk - 1``,
    and ``L`` positions touch at most ``ceil((L - 1) / page_size) + 1``
    pages."""
    return -(-(window - 1 + chunk) // page_size) + 1


class WindowPages:
    """The window layers' pool and the requests' page lists in it."""

    def __init__(self, *, window: int, chunk: int, page_size: int,
                 max_batch: int, pages_per_req: int):
        self.window = int(window)
        self.page_size = int(page_size)
        # a short context never reaches the cap
        self.cap = min(pages_cap(window, chunk, page_size), pages_per_req)
        # page 0 is the pool's scratch page, as in the full layers' pool
        self.pool = BlockPool(max_batch * self.cap + 1, page_size)
        self.reserved = 0
        self.released_total = 0

    @property
    def usable(self) -> int:
        return self.pool.num_pages - 1

    def reserve(self, r, total_tokens: int) -> bool:
        """Promise ``r`` the most pages it will ever hold at once, or refuse
        (admission then fails as it does on the full layers' pool)."""
        need = min(self.cap, -(-total_tokens // self.page_size))
        if self.reserved + need > self.usable:
            return False
        self.reserved += need
        r.wreserved = need
        return True

    def release(self, r) -> None:
        if r.wpages:
            self.pool.decref(r.wpages)
        self.reserved -= r.wreserved
        r.wpages, r.wfirst, r.wreserved = [], 0, 0

    def first_page(self, kv_len: int) -> int:
        """The first page index a request of ``kv_len`` tokens still needs:
        the page of position ``kv_len - window``."""
        return max(kv_len - self.window, 0) // self.page_size

    def trim(self, r) -> int:
        """Release ``r``'s pages wholly behind ``kv_len - window``."""
        n = min(self.first_page(r.kv_len) - r.wfirst, len(r.wpages))
        if n <= 0:
            return 0
        self.pool.decref(r.wpages[:n])
        del r.wpages[:n]
        r.wfirst += n
        self.released_total += n
        return n

    def assemble(self, sched: Sequence[Tuple[object, int]], running,
                 rung: int, max_batch: int):
        """One step's window-side schedule.  ``sched``: the scheduled
        ``(request, n)`` in flat-row order, each request's ``kv_len``
        already advanced by its ``n``.  Extends the page lists, and returns
        (arrays for the step: ``token_page`` ``[rung]``, the window pool's
        page of each row; ``page_table`` ``[max_batch, cap]``; ``first_tok``
        ``[max_batch]``, the position of each slot's first held row,
        (request, row0, n, kv_after counted from ``first_tok``) per
        scheduled request for the planner, pages held)."""
        ps = self.page_size
        token_page = np.zeros(rung, np.int32)   # scratch page 0 for pads
        page_table = np.zeros((max_batch, self.cap), np.int32)
        first_tok = np.zeros(max_batch, np.int32)
        local = []
        row = 0
        for r, n in sched:
            if r.kv_len - n == 0 and (r.wpages or r.wfirst):
                raise RuntimeError(f"{r.rid}: window pages outlived a "
                                   "release")
            want = (r.kv_len - 1) // ps + 1 - r.wfirst
            if want > len(r.wpages):
                fresh = self.pool.alloc(want - len(r.wpages))
                if fresh is None or want > r.wreserved:
                    raise RuntimeError(
                        f"{r.rid}: {want} window pages wanted, "
                        f"{r.wreserved} reserved, {self.pool.free_pages} "
                        "free: the pool's cap does not hold")
                r.wpages.extend(fresh)
            p0 = r.kv_len - n
            token_page[row:row + n] = np.asarray(r.wpages, np.int32)[
                np.arange(p0, r.kv_len) // ps - r.wfirst]
            local.append((r, row, n, r.kv_len - r.wfirst * ps))
            row += n
        for r in running:
            page_table[r.slot, :len(r.wpages)] = r.wpages
            first_tok[r.slot] = r.wfirst * ps
        held = sum(len(r.wpages) for r in running)
        return (dict(token_page=token_page, page_table=page_table,
                     first_tok=first_tok), local, held)

    def lower(self, sched, running, *, rung: int, max_batch: int,
              geom, span):
        """What a step's program reads for the window layers, as host
        arrays (it rides the model's plan, ``mplan["window"]``, so it
        crosses in the handover's one buffer), the planner's stats (None on
        the reference tier) and the step's facts.  ``geom``: the window
        layers' launch geometry on the kernel tier, else None;
        ``span(name)`` opens a child span of the step's ``lower`` phase
        (the planner's levels go under ``lower.build.window``)."""
        from flashinfer_tpu.serve import engine_kernels as _ek

        arrays, local, held = self.assemble(sched, running, rung, max_batch)
        stats = None
        if geom is not None:
            segs = [_ek.SchedSeg(
                row0=row0, n=n, pages=tuple(r.wpages), split=0,
                kv_after=kv_after, decoding=r.kv_len - n >= len(r.prompt),
                slot=r.slot, group=i)
                for i, (r, row0, n, kv_after) in enumerate(local)]
            with span("lower.build"):
                plans = _ek.build_engine_work_units(
                    segs, rung=rung, geom=geom,
                    window_left=self.window - 1,
                    span=lambda n: span(f"lower.build.window.{n}"))
            stats = plans["stats"]
            with span("lower.upload"):
                arrays["plans"] = _ek.window_plans_to_host(plans)
        return arrays, stats, {
            "window_pages_held": held, "window_pages_pool": self.usable,
            "window_pages_share": 100.0 * held / self.usable}
