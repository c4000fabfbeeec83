"""Pallas work-unit lowering of the serving engine's attention step.

The continuous-batching engine (serve/engine.py) schedules mixed decode
+ chunked-prefill tokens on one flat axis with a two-level cascade
decomposition; its reference backend runs that attention in dense XLA
form.  This module lowers the SAME per-step schedule onto the work-unit
kernels, a unit only where a launch has work:

- :func:`build_engine_work_units` — the host-side planner.  It takes
  the engine's flat-row schedule segments (tokens/positions, group page
  runs, per-token window bounds, the cascade split) and lowers them
  into the existing plan-array forms:

  * **level 0** (the shared-prefix span, gathered once per prefix
    GROUP of two or more segments) and **level 1 chunked prefill**
    (each request's window, ``q_len >= 1``) both become
    :func:`~flashinfer_tpu.ops.paged_prefill.build_prefill_work_units`
    plans — level 0 with a per-token custom mask encoding the
    causal-by-global-position rule ``kv_row <= pos`` over the group
    run, level 1 with the planner's native causal rule: over the
    suffix of a chunk that shares its run (suffix-local positions,
    negative ``qpos0`` rows inside the shared span attend nothing and
    emit the empty-state sentinel), over the WHOLE context from page 0
    of a chunk alone in its group (global positions: the same rule as
    level 0's mask, as integer bounds a unit and no bitmap; the rows of
    such a chunk PAST its split, the prompt's last page of rows at most,
    stay the cascade's: the run at level 0, the suffix here);
  * **decode tokens** (``q_len == 1``) become
    :func:`~flashinfer_tpu.ops.paged_decode.build_decode_split_units`
    units (split-KV partials + ``merge_states``): over its suffix page
    run for a lane whose group holds another row this step, over its
    WHOLE page run, with no level-0 unit, for a lane alone in its group
    (a decode lane costs decode units only).

  A group that is ONE segment this step has nothing for level 0 to
  de-duplicate, so it leaves level 0 with nothing: no unit, no ``pos``
  list, no ``[rows x split]`` mask part.  The planner makes that choice
  per group from the schedule it is given: riders of one cached run, and
  a chunk beside its sharers, keep level 0 (the cascade's dedup), and a
  segment turns lone or shared from one step to the next.  One part of a
  lone chunk does not move: the rows past its split (the chunk that ends
  a prompt has 1 to ``page_size`` of them, the prompt's last row among
  them) attended the run at level 0 and the suffix at level 1 and were
  merged, and they still are, under a mask part of ones that the planner
  demotes to maskless units.  One pass over run and suffix is the same
  softmax to rounding, not to the bit, and the prompt's last row samples
  the request's first token.

- :func:`engine_kernel_attention` — the in-jit composition: the three
  kernel launches produce per-level ``(out, lse)`` states and fold
  through the SAME :func:`~flashinfer_tpu.cascade.compose_cascade_levels`
  merge operator the reference backend uses, so the cascade
  decomposition — and the shared-run HBM dedup it exists for — is
  identical across backends.

Retrace contract (the engine's rung ladder): every plan-array shape is
a pure function of the RUNG, never the schedule values — prefill plans
pad to :meth:`EngineKernelGeom.prefill_unit_cap` via the planner's
``num_units_pad`` (the two launches run only the plan's real units: its
``n_units`` leaf is the trip count of their unit axis, a value and not a
shape), the decode plan is ``max_batch x num_splits`` units over a
fixed-width page table, and the level-0 custom-mask operand is always
present (all-ones windows demote to the maskless PARTIAL code in-plan;
a plan with no mask unit carries the planner's read-only view of one zero
byte in the rung's ``[U, block_q, mb]``, not that much allocated).
One rung == one trace, the <= 9-trace budget the reference backend pins.

Every flat row — scheduled or rung padding — is covered by a plan
segment (padding, decode rows at level 1 and lone segments at level 0
ride ``kv_len = 0`` segments), so both levels emit defined ``(0, -inf)``
empty states for rows that attend nothing there: the reset state of a
tile, which needs a unit of its own only where it holds no other.

Those shapes are what the step's program takes, not what crosses to the
device: :func:`plans_to_host` sends of the level-0 bitmap the rows of
the units that read one, and :func:`plans_on_device` spreads them into
the rung's ``[U, block_q, mb]`` there (serve/handover.py has the rest).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flashinfer_tpu.utils import cdiv, next_power_of_two, round_up


@dataclasses.dataclass(frozen=True)
class SchedSeg:
    """One scheduled (request, chunk) of the engine's step, in flat-row
    order: rows ``[row0, row0 + n)`` of the rung-padded token axis.

    ``pages`` is the request's full page run, ``split`` the page-aligned
    cascade boundary frozen at first admission, ``kv_after`` the
    request's KV length AFTER this step's append (the last row's
    position + 1).  ``group`` is the step-local shared-prefix group id —
    the engine orders segments so equal groups are adjacent, which is
    what lets level 0 gather each shared run once per contiguous span."""

    row0: int
    n: int
    pages: Tuple[int, ...]
    split: int
    kv_after: int
    decoding: bool
    slot: int
    group: int


@dataclasses.dataclass(frozen=True)
class EngineKernelGeom:
    """The kernel backend's frozen launch geometry — every static the
    three launches need, derived ONCE from the engine config so plan
    shapes (and therefore traces) are functions of the rung alone."""

    page_size: int
    pages_per_req: int
    max_batch: int
    block_q: int
    prefill_ppc: int     # kv pages per DMA chunk, both prefill levels
    decode_ppc: int      # split-decode chunk pages (split_pages_per_chunk)
    num_splits: int      # decode split factor (1 = unsplit degenerate)
    single_chunk: bool   # config-level certificate: every decode unit
    #                      is at most one DMA chunk for EVERY schedule
    dec_width: int       # fixed decode page-table width (chunk-aligned)

    @staticmethod
    def build(*, page_size: int, pages_per_req: int, max_batch: int,
              max_rung: int, num_kv_heads: int, head_dim: int,
              kv_itemsize: int, num_splits: int = 1
              ) -> "EngineKernelGeom":
        from flashinfer_tpu.ops.paged_decode import split_pages_per_chunk
        from flashinfer_tpu.ops.paged_prefill import (
            ingest_block_q, ingest_pages_per_chunk)

        block_q = ingest_block_q(max_rung)
        prefill_ppc = ingest_pages_per_chunk(page_size)
        decode_ppc = split_pages_per_chunk(
            page_size, num_kv_heads, head_dim, kv_itemsize)
        per_unit_pages = round_up(cdiv(pages_per_req, num_splits),
                                  decode_ppc)
        return EngineKernelGeom(
            page_size=page_size,
            pages_per_req=pages_per_req,
            max_batch=max_batch,
            block_q=block_q,
            prefill_ppc=prefill_ppc,
            decode_ppc=decode_ppc,
            num_splits=num_splits,
            single_chunk=cdiv(pages_per_req, num_splits) <= decode_ppc,
            dec_width=max(round_up(pages_per_req, decode_ppc),
                          per_unit_pages * num_splits, decode_ppc),
        )

    @property
    def max_prefill_chunks(self) -> int:
        return max(cdiv(self.pages_per_req, self.prefill_ppc), 1)

    def prefill_unit_cap(self, rung: int) -> int:
        """Worst-case work units either prefill-level plan can need at
        one rung: each of the <= max_batch scheduled segments (plus the
        trailing padding segment) overlaps at most ``cdiv(n, block_q) +
        1`` qo tiles, each (tile, segment) span walks at most
        ``max_prefill_chunks`` KV chunks, and a tile of pruned spans keeps
        one zero-write fallback unit.  (A lone chunk that ends its prompt
        is TWO plan segments, its rows on either side of the split: a
        boundary inside a tile adds one (tile, segment) span, and ``2 x
        max_batch`` boundaries are counted here.)  The cap is a RUNG
        static, so a rung's plan shape can never vary step to step.  It
        sizes the plan arrays only: a launch runs the units a step's plan
        has."""
        spans = cdiv(rung, self.block_q) + 2 * (self.max_batch + 1)
        return max(next_power_of_two(spans * self.max_prefill_chunks), 8)

    def mask_unit_cap(self, rung: int) -> int:
        """Level-0 units whose bitmap rows a step sends by themselves
        (:func:`plans_to_host`): the units on the diagonal of a chunk
        prefilled inside its own span, two or so a q tile.  A step with
        more sends the whole bitmap."""
        return min(32, self.prefill_unit_cap(rung))


# plan-dict keys that ride into the jitted engine body (arrays only —
# statics and stats stay host-side so the traced pytree is rung-stable)
PREFILL_ARRAY_KEYS = ("qstart", "rowlo", "rowhi", "qpos0", "kvstart",
                      "kvlen", "first", "wout", "qslot", "code", "pages",
                      "n_units", "mask_bytes")
DECODE_ARRAY_KEYS = ("pages", "kvlen", "wu_req", "wu_page0", "wu_kvlen")


def build_engine_work_units(
    segs: Sequence[SchedSeg],
    *,
    rung: int,
    geom: EngineKernelGeom,
    window_left: int = -1,
    span=None,
):
    """Lower one engine step's schedule into the three plan-array forms.

    ``segs`` must tile ``[0, total)`` of the flat axis contiguously and
    keep equal ``group`` ids adjacent (the engine's group-sorted
    schedule).  Returns a dict::

        prefill0  — level-0 shared-prefix plan (custom mask, causal by
                    global position; one qo segment per contiguous
                    group span, so the run's pages stream once per
                    (tile, chunk) for ALL riders — the cascade dedup;
                    a group that is ONE segment this step, a decoding
                    lane or a prompt chunk, rides a kv_len=0 segment:
                    no unit, no mask part; but a lone chunk's rows
                    PAST its split are a segment of their own over the
                    run, every column, under a part of ones)
        prefill1  — level-1 plan (causal): a lone chunk's WHOLE context
                    from page 0 (``kv_len = kv_after``, so ``qpos0`` is
                    the chunk's first GLOBAL position; ``kv_len =
                    split`` for the rows inside the span of the chunk
                    that ends its prompt, whose rows past the split are
                    a second segment over the suffix), a sharer's
                    suffix; decode rows and a sharer's fully-in-span
                    chunk ride kv_len=0 segments
        decode    — split-KV decode plan over per-slot tables: a lone
                    lane's whole context from page 0, a sharer's suffix
        dec_rows  — [max_batch] flat row of each decode lane's token
                    (== rung for idle slots: gathers clip to a harmless
                    row, scatters drop)
        stats     — launched-vs-effective unit accounting for the
                    ``engine_step`` cost family (a prefill launch runs
                    its plan's real units: what is launched and not
                    effective is the idle rows and columns of a tile),
                    the prefill plans' ``prefill_fallback_units``,
                    ``decode_lanes_whole`` and ``prefill_tokens_whole``
                    (the lanes, and the prompt tokens inside the spans
                    of the chunks, that were alone in their groups; 0
                    in a window plan, which never had a level 0 to
                    leave)

    Every array shape depends only on ``rung`` and ``geom``.

    ``window_left >= 0`` is the plan of a model's WINDOW layers
    (serve/window_pool.py): ``segs`` then carry each request's window
    page list and positions counted from that list's first page, with
    ``split == 0``: no level 0 (no ``prefill0`` in the result), a prompt
    chunk rides level 1 under the planner's own window rule, a decode
    lane the split-KV units over the pages it still holds.

    ``span(name)`` opens a child span of the caller's (``l0``: group walk,
    mask parts, level 0's plan; ``l1``; ``decode``); None: the gate is off.
    """
    from flashinfer_tpu.obs import child_span
    from flashinfer_tpu.ops.paged_decode import build_decode_split_units
    from flashinfer_tpu.ops.paged_prefill import build_prefill_work_units

    ps = geom.page_size
    total = segs[-1].row0 + segs[-1].n if segs else 0
    if total > rung:
        raise ValueError(f"schedule has {total} rows > rung {rung}")
    row = 0
    for s in segs:
        if s.row0 != row:
            raise ValueError("schedule segments must tile the flat axis "
                             f"contiguously (row {row} != seg.row0 "
                             f"{s.row0})")
        row += s.n
    U = geom.prefill_unit_cap(rung)
    windowed = window_left >= 0
    if windowed and any(s.split for s in segs):
        raise ValueError("a window plan has no shared level: split 0")

    # ---- level 0: shared-prefix groups, causal-by-global-position ----
    # (a window plan has none: every segment's would be empty)
    plan0 = None
    # slots of the segments alone in their groups: their level-1 or decode
    # units start at page 0
    whole = set()
    # of a lone chunk, the rows PAST its split, by slot: they keep level 0
    # over the run and the suffix at level 1 (see below)
    past: Dict[int, int] = {}
    if not windowed:
        with child_span(span, "l0"):
            qo0 = [0]
            kv0_lens: List[int] = []
            pi0 = [0]
            pages0: List[int] = []
            mask_parts: List[np.ndarray] = []

            def _close_seg0(n_rows, kv_len=0, pages=()):
                qo0.append(qo0[-1] + n_rows)
                kv0_lens.append(kv_len)
                pages0.extend(pages)
                pi0.append(len(pages0))

            i = 0
            while i < len(segs):
                s = segs[i]
                j = i + 1
                while j < len(segs) and segs[j].group == s.group:
                    j += 1
                group = segs[i:j]
                rows = sum(e.n for e in group)
                # a group that is ONE segment this step shares its run
                # with no other row: nothing to dedup.  A lane's decode
                # units, a chunk's level-1 units take its whole context
                # (a one-row unit a chunk through a block_q tile is the
                # dearest way to read one; a chunk's causal rule is level
                # 1's own arithmetic, no [rows x split] mask to build)
                lone = j - i == 1
                if lone:
                    whole.add(s.slot)
                run = s.pages[:s.split // ps]
                if run and not lone:
                    pos = np.concatenate([
                        np.arange(e.kv_after - e.n, e.kv_after)
                        for e in group])
                    mask_parts.append((np.arange(s.split)[None, :]
                                       <= pos[:, None]).reshape(-1))
                    _close_seg0(rows, s.split, run)
                elif run and not s.decoding and s.kv_after > s.split:
                    # the lone chunk that ends its prompt: its last rows
                    # (a page of them at most) lie PAST the split and attend
                    # the run AND the suffix.  They stay two states merged,
                    # the run here (every column: a part of ones, which
                    # the planner demotes to maskless units) and the
                    # suffix at level 1, so the token sampled from the
                    # prompt's last row is the cascade's bit for bit.  The
                    # rows inside the span leave level 0 like any other
                    n_past = min(s.kv_after - s.split, s.n)
                    past[s.slot] = n_past
                    if s.n > n_past:
                        _close_seg0(s.n - n_past)
                    mask_parts.append(np.ones(n_past * s.split, bool))
                    _close_seg0(n_past, s.split, run)
                else:
                    # no shared span (split == 0) or a lone segment: level
                    # 0 is empty for these rows: a kv_len=0 segment emits
                    # (0, -inf)
                    _close_seg0(rows)
                i = j
            if total < rung:  # rung padding rows: defined zeros, empty state
                _close_seg0(rung - total)
            mask0 = (np.concatenate(mask_parts) if mask_parts
                     else np.zeros(0, bool))
            plan0 = build_prefill_work_units(
                np.asarray(qo0, np.int64), np.asarray(pi0, np.int64),
                np.asarray(pages0, np.int64), np.asarray(kv0_lens, np.int64),
                geom.block_q, geom.prefill_ppc, ps,
                mask_flat=mask0, causal=False, window_left=-1,
                pack_tiles=True, prune=True, num_units_pad=U,
            )

    # ---- level 1: per-segment windows, native causal rule: a sharer's
    # suffix, a lone chunk's whole context (global positions) ----
    with child_span(span, "l1"):
        qo1 = [0]
        kv1_lens: List[int] = []
        pi1 = [0]
        pages1: List[int] = []

        def _close_seg1(n_rows, lo=0, hi=0, pages=()):
            qo1.append(qo1[-1] + n_rows)
            kv1_lens.append(max(hi - lo, 0))
            if hi > lo:
                pages1.extend(pages[lo // ps: cdiv(hi, ps)])
            pi1.append(len(pages1))

        for s in segs:
            if s.decoding:
                _close_seg1(s.n)
            elif s.slot in past:
                # rows inside the span: the run from page 0, causally;
                # rows past the split: the suffix, level 0 has the run
                if s.n > past[s.slot]:
                    _close_seg1(s.n - past[s.slot], 0, s.split, s.pages)
                _close_seg1(past[s.slot], s.split, s.kv_after, s.pages)
            else:
                _close_seg1(s.n, 0 if s.slot in whole else s.split,
                            s.kv_after, s.pages)
        if total < rung:
            _close_seg1(rung - total)
        plan1 = build_prefill_work_units(
            np.asarray(qo1, np.int64), np.asarray(pi1, np.int64),
            np.asarray(pages1, np.int64), np.asarray(kv1_lens, np.int64),
            geom.block_q, geom.prefill_ppc, ps,
            causal=True, window_left=window_left,
            pack_tiles=True, prune=True, num_units_pad=U,
        )

    # ---- decode lanes: split-KV units over per-slot page tables ----
    with child_span(span, "decode"):
        dec_table = np.zeros((geom.max_batch, geom.dec_width), np.int32)
        dec_lens = np.zeros(geom.max_batch, np.int64)
        dec_rows = np.full(geom.max_batch, rung, np.int32)  # rung == idle
        for s in segs:
            if not s.decoding:
                continue
            # a sharer's units cover its suffix (level 0 has the run)
            lo = 0 if s.slot in whole else s.split
            lane_pages = s.pages[lo // ps: cdiv(s.kv_after, ps)]
            dec_table[s.slot, :len(lane_pages)] = lane_pages
            dec_lens[s.slot] = s.kv_after - lo
            dec_rows[s.slot] = s.row0
        dplan = build_decode_split_units(
            dec_table, dec_lens, num_splits=geom.num_splits,
            page_size=ps, pages_per_chunk=geom.decode_ppc,
        )
    assert dplan["pages"].shape == (geom.max_batch, geom.dec_width), \
        (dplan["pages"].shape, geom.dec_width)
    assert not geom.single_chunk or dplan["single_chunk"]

    # launched against useful, fallback units, and what rode whole: lanes on
    # decode units alone, chunks' tokens on level 1 alone
    alone = [s for s in segs if s.slot in whole]
    stats = _plan_stats(
        plan0, plan1, dplan, dec_lens, geom,
        lanes_whole=sum(s.decoding for s in alone),
        tokens_whole=sum(s.n - past.get(s.slot, 0)
                         for s in alone if not s.decoding))
    out = dict(prefill1=plan1, decode=dplan, dec_rows=dec_rows, stats=stats)
    return out if windowed else dict(out, prefill0=plan0)


def plans_to_host(plans: Dict, mask_cap: Optional[int] = None) -> Dict:
    """What a plan bundle's launches read, as host arrays: array leaves
    only (statics/stats stripped), in a FIXED key layout.

    Level 0's bitmap is ``[U, block_q, mb]`` (33.5 MB at the cells'
    statics) and the launch expands it for ``CODE_PARTIAL_MASK`` units
    alone, a handful a step.  Where a step has at most ``mask_cap`` of
    them the bundle carries their rows (``mask_rows``, ``[mask_cap,
    block_q, mb]``) and unit indices (``mask_idx``, unused entries out
    of range) in ``mask_bytes``' place, for :func:`plans_on_device` to
    spread; a step with more, or ``mask_cap=None``, carries the whole
    array."""
    from flashinfer_tpu.ops.paged_prefill import CODE_PARTIAL_MASK

    p0 = plans["prefill0"]
    lvl0 = {k: p0[k] for k in PREFILL_ARRAY_KEYS if k != "mask_bytes"}
    units = np.flatnonzero(p0["code"] == CODE_PARTIAL_MASK)
    if mask_cap is None or len(units) > mask_cap:
        lvl0["mask_bytes"] = p0["mask_bytes"]
    else:
        idx = np.full(mask_cap, len(p0["code"]), np.int32)
        idx[:len(units)] = units
        rows = np.zeros((mask_cap,) + p0["mask_bytes"].shape[1:], np.uint8)
        rows[:len(units)] = p0["mask_bytes"][units]
        lvl0["mask_idx"], lvl0["mask_rows"] = idx, rows
    return dict(
        prefill0=lvl0,
        prefill1={k: plans["prefill1"][k]
                  for k in PREFILL_ARRAY_KEYS if k != "mask_bytes"},
        decode={k: plans["decode"][k] for k in DECODE_ARRAY_KEYS},
        dec_rows=plans["dec_rows"],
    )


def window_plans_to_host(plans: Dict) -> Dict:
    """:func:`plans_to_host` for a window plan (``build_engine_work_units``
    with ``window_left``): level 1 and the decode units, no bitmap."""
    return dict(
        prefill1={k: plans["prefill1"][k]
                  for k in PREFILL_ARRAY_KEYS if k != "mask_bytes"},
        decode={k: plans["decode"][k] for k in DECODE_ARRAY_KEYS},
        dec_rows=plans["dec_rows"],
    )


def plans_on_device(kplans: Dict) -> Dict:
    """The rung-stable traced pytree of a plan bundle, from
    :func:`plans_to_host`'s tree once it is on the device: the step's
    leaves, dtypes and shapes whichever way the bitmap crossed.  Bitmap
    rows are spread into zeros ``[U, block_q, mb]``, ``mask_rows[i]`` at
    unit ``mask_idx[i]`` (an index out of range writes nothing): outside
    the step's program, in the one that cuts its inputs."""
    p0 = dict(kplans["prefill0"])
    if "mask_rows" in p0:
        idx, rows = p0.pop("mask_idx"), p0.pop("mask_rows")
        p0["mask_bytes"] = jnp.zeros(
            p0["code"].shape + rows.shape[1:], rows.dtype
        ).at[idx].set(rows, mode="drop")
    return dict(kplans, prefill0=p0)


def step_inputs_on_device(host: Dict) -> Dict:
    """:func:`plans_on_device` over the ``kplans`` of a step's inputs
    (what ``serve/handover.py`` runs inside its cutting program)."""
    return dict(host, kplans=plans_on_device(host["kplans"]))


def plans_to_device(plans: Dict) -> Dict:
    """A plan bundle's traced pytree the plain way: every leaf, the whole
    bitmap, a buffer each."""
    return jax.device_put(plans_to_host(plans))


def _level1_and_decode(q, k_cache, v_cache, kplans, geom, sm_scale,
                       window_left):
    """Level 1's ``(out, lse)`` with the decode lanes' rows scattered in."""
    from flashinfer_tpu.ops.paged_decode import paged_decode_attention_split
    from flashinfer_tpu.ops.paged_prefill import fused_paged_prefill

    p1, pd = kplans["prefill1"], kplans["decode"]
    o1, lse1 = fused_paged_prefill(
        q, k_cache, v_cache, p1,
        num_units=p1["qstart"].shape[0], block_q=geom.block_q,
        pages_per_chunk=geom.prefill_ppc, sm_scale=sm_scale,
        causal=True, window_left=window_left, return_lse=True,
        name="fused_paged_prefill_l1")
    dec_rows = kplans["dec_rows"]
    # idle lanes carry row == rung: the gather clips to a real row whose
    # q is then attended against a kv_len=0 table (empty state), and
    # the scatter back drops out-of-bounds lanes entirely
    qd = q[dec_rows]
    od, lsed = paged_decode_attention_split(
        qd, k_cache, v_cache, pd,
        num_units=pd["wu_req"].shape[0], num_splits=geom.num_splits,
        single_chunk=geom.single_chunk,
        pages_per_chunk=geom.decode_ppc, sm_scale=sm_scale,
        window_left=window_left, return_lse=True)
    o1 = o1.at[dec_rows].set(od.astype(o1.dtype), mode="drop")
    lse1 = lse1.at[dec_rows].set(lsed.astype(lse1.dtype), mode="drop")
    return o1, lse1


def engine_window_attention(q, k_cache, v_cache, wplans, *,
                            geom: EngineKernelGeom, sm_scale: float,
                            window_left: int):
    """One WINDOW layer's attention on the work units of its own plan
    (``build_engine_work_units`` with ``window_left``): level 1 under the
    window rule and the decode lanes' split-KV units over the pages the
    window pool still holds; no level 0, so nothing to merge.  f32
    ``[T, H, D]``."""
    return _level1_and_decode(q, k_cache, v_cache, wplans, geom, sm_scale,
                              window_left)[0]


def engine_kernel_attention(q, k_cache, v_cache, kplans, *,
                            geom: EngineKernelGeom, sm_scale: float):
    """One layer's engine attention on the Pallas work units (traced
    inside the engine's jitted body): level-0 + level-1 prefill
    launches, the split-KV decode launch scattered into level 1, and
    the cascade merge fold.  Returns f32 ``[T, H, D]`` (the same
    contract as the reference backend's compose output; int8-KV
    v_scale is applied by the caller after the merge, which is exact
    because merging is linear in V)."""
    from flashinfer_tpu.cascade import compose_cascade_levels
    from flashinfer_tpu.ops.paged_prefill import fused_paged_prefill

    p0 = kplans["prefill0"]
    o0, lse0 = fused_paged_prefill(
        q, k_cache, v_cache, p0,
        num_units=p0["qstart"].shape[0], block_q=geom.block_q,
        pages_per_chunk=geom.prefill_ppc, sm_scale=sm_scale,
        causal=False, return_lse=True, name="fused_paged_prefill_l0")
    o1, lse1 = _level1_and_decode(q, k_cache, v_cache, kplans, geom,
                                  sm_scale, -1)
    out, _ = compose_cascade_levels([(o0, lse0), (o1, lse1)])
    return out


def _plan_stats(plan0, plan1, dplan, dec_lens, geom, lanes_whole,
                tokens_whole) -> Dict:
    """``build_engine_work_units``' ``stats``: what a step's launches run
    against what of it is useful, and the three counts of how the planner
    placed the work (``plan0`` None: a window plan, no level 0)."""
    from flashinfer_tpu.ops.paged_prefill import CODE_PARTIAL_MASK

    ps = geom.page_size
    chunk_tokens = geom.prefill_ppc * ps
    # what the prefill launches run: each level's ``n_units`` (the pad
    # units up to the rung's cap are not run)
    levels = [plan1] if plan0 is None else [plan0, plan1]
    launched = sum(int(p["n_units"]) for p in levels)
    return {
        "prefill_units": sum(p["stats"]["units"] for p in levels),
        "prefill_units_launched": launched,
        # of those, units that attend nothing: a tile's lone write-back
        "prefill_fallback_units": sum(
            p["stats"]["units_fallback"] for p in levels),
        "prefill_cells_launched": float(
            launched * geom.block_q * chunk_tokens),
        "prefill_cells_valid": float(sum(
            p["stats"]["mxu_cells_valid"] for p in levels)),
        "prefill_rows_launched": float(launched * chunk_tokens),
        "decode_pages_real": dplan["stats"]["pages_real"],
        "decode_pages_launched": dplan["stats"]["pages_launched"],
        "decode_rows_launched": float(
            dplan["stats"]["pages_launched"] * ps),
        "decode_cells_launched": float(
            dplan["stats"]["pages_launched"] * ps),
        "decode_cells_valid": float(dec_lens.sum()),
        # lanes whose whole context rode decode units (no level-0 unit)
        "decode_lanes_whole": lanes_whole,
        # prompt tokens that rode level 1 whole (no level-0 unit): a lone
        # chunk's rows inside its span
        "prefill_tokens_whole": tokens_whole,
        # level-0 units whose bitmap the launch expands
        "mask_units": 0 if plan0 is None else int(np.count_nonzero(
            plan0["code"] == CODE_PARTIAL_MASK)),
    }
