"""Fused paged-KV batch prefill Pallas kernel (pipelined work units).

The TPU translation of the reference's prefill work queue
(``PrefillPlan``/``PrefillSplitQOKVIndptr``, scheduler.cuh:545-897 +
``BatchPrefillWithPagedKVCacheDispatched``, prefill.cuh:4057): the plan
splits every request into (qo-tile, kv-chunk) work units; the kernel walks
the unit list sequentially with an explicitly pipelined mainloop:

- **Double-buffered KV streaming.** The next unit's KV pages are DMA'd
  HBM->VMEM while the current unit's MXU dots run (two chunk slots, one
  semaphore per page copy) — the copy never serializes with compute.
- **Double-buffered q streaming.** q tiles are fetched once per tile (not
  per unit) into the slot the plan assigned (``qslot``, tile parity); the
  fetch for the next tile is issued at the current tile's last unit, so it
  overlaps that unit's compute.  The wait lands on the next tile's first
  unit (``first`` doubles as the q-wait flag).
- **Plan-time mask hoisting.** ``build_prefill_work_units`` classifies
  every unit with a block code — ``FULL`` (every position provably valid:
  no mask math at all in-kernel), ``PARTIAL`` (bounds/causal/window
  recomputed in-register), ``PARTIAL_MASK`` (additionally expands the
  per-unit packed custom-mask bitmap) — and *prunes* units that are
  provably all-masked (causal chunks above the diagonal, sliding-window
  chunks below the window, custom-mask windows with no set bit).  The
  inner loop never discovers dead work; the plan already removed it —
  the same block-sparsity the reference gets from its work-queue plan.
- **Work-unit packing.** With ``pack_tiles=True`` (default) qo tiles are
  aligned segments of the *global* flattened token axis, so short
  requests coalesce into full tiles: one q fetch and one output
  write-back serve every request overlapping the tile, and each
  (tile, request, chunk) unit masks to its row span ``[rowlo, rowhi)``.
  Rows outside the span contribute ``p = 0, alpha = 1`` identity steps
  to the online softmax, so packed and unpacked plans produce
  BIT-IDENTICAL outputs (pinned by tests/test_pipelined_prefill.py).
  Padding waste (idle unit rows / idle MXU cells) is reported through
  the plan's ``stats`` into the obs padding-waste histograms.

Grid is ``(num_kv_heads, n_units)``: each unit computes ALL q heads of
one KV head's GQA group, so every KV page is fetched from HBM exactly once
per kv head — the same bandwidth discipline as the decode kernel.  The
unit axis runs the plan's REAL units only (``plan["n_units"]``, a value
of the call, not of the trace): the plan arrays keep their padded static
length, so one trace serves every unit count, and pad units are never run.

vs the gather+flash path (prefill.py): no extra HBM round trip for KV —
for chunked prefill (small qo vs large kv) the gather pass costs ~50% of
the attention time, which this kernel eliminates.

Correctness invariant (relied on by the unpacked partial-tile
write-back): units are ordered by ascending qstart, and the unit grid
dimension executes sequentially — an unpacked partial tile's full-block
output DMA may clobber the next request's rows, which later units then
rewrite (packed tiles are disjoint and never clobber).
``build_prefill_work_units`` asserts the ordering; do not mark the unit
dim "parallel".

The plan's ``causal``/``window_left`` MUST match the kernel call's: the
plan prunes and FULL-codes units under those rules, so a mismatched
kernel call would double-apply or miss masking.  The paged-prefill
wrapper passes both from one place.

Hardware-validated on v5e (tests/test_tpu_hw.py — mixed ragged batch with
append semantics vs dense oracle) and the default paged-prefill backend
for HND caches; the GQA group rides one merged [bq*group, chunk] MXU dot.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import (cdiv, next_power_of_two, round_up, use_interpret)

_NEG_INF = -1e30

# plan-time block codes (the hoisted mask descriptors): the kernel
# specializes its softmax update on these instead of recomputing
# validity for provably-full blocks
CODE_FULL = 0  # every (row, col) valid — no mask math in-kernel
CODE_PARTIAL = 1  # bounds/causal/window recomputed in-register
CODE_PARTIAL_MASK = 2  # PARTIAL + packed custom-mask bitmap expansion
CODE_WRITE_ONLY = 3  # ingest mode only: quantize-append the chunk, no
#                      attention (empty row span; the chunk was pruned
#                      from every tile but its K/V must reach the cache)

# Mosaic slices a tiled dimension at a dynamic offset only when it can
# prove the offset a multiple of the tile's sublane rows (8 of f32, 16
# of bf16).  The ingest kernel DMAs each chunk of RAW rows from
# ``[kvbase + kvstart, +chunk)`` of the flat axis, so a compiled launch
# needs every request's flat-axis base (and the chunk width) on this
# alignment; the planner reports it as ``stats["ingest_kv_aligned"]``.
INGEST_KV_ALIGN = 16

_POPCNT = np.array([bin(i).count("1") for i in range(256)], np.int64)


def ingest_rows_aligned(kv_bases, chunk_tokens: int) -> bool:
    """Whether every raw-row DMA source of an ingest plan over these
    per-request flat-axis bases starts on :data:`INGEST_KV_ALIGN` — the
    fact every fused-ingest adopter gates the compiled launch on."""
    return chunk_tokens % INGEST_KV_ALIGN == 0 and not np.any(
        np.asarray(kv_bases, np.int64) % INGEST_KV_ALIGN)


def mask_lane_bytes(chunk_tokens: int) -> int:
    """Lane width of the per-unit packed-mask bitmap (>= 128 for Mosaic
    VMEM blocks)."""
    return max(round_up(cdiv(chunk_tokens, 8), 128), 128)


def block_candidates(page_size: int):
    """THE ``fused_prefill.blocks`` autotune candidate grid — consumed by
    both the wrapper's in-run tuner (prefill.py) and the offline sweep
    (benchmarks/bench_prefill_blocks.py) so the two can never explore
    diverging spaces.  chunk_tokens stays <= 256: each unit unrolls 2
    DMAs/page and ppc=16 (32 in-flight) is the on-chip-validated queue
    ceiling — ppc=32 would be the W002 queue-unroll wedge class.
    block_q is DMA-count-neutral, so it explores up to 512."""
    return sorted({
        (bq, max(1, ct // page_size))
        for bq in (64, 128, 256, 512) for ct in (128, 256)
    })


def _normalize_mask(mask_flat, mask_total_bits, qo_indptr, kv_lens):
    """Validate the flat mask concat; -> (unpacked bool bits, the
    caller's original packed/bool form for the zero-repack native path,
    total_bits, per-request bit offsets).

    The bool view feeds the plan-time classification (mask summaries,
    pruning); the original form goes straight to the C++ planner, which
    reads LSB-first packed bytes directly — re-packing the bool view
    would be an O(total bits) pass on the hottest host-plan loop."""
    if mask_total_bits is None:
        if mask_flat.dtype == np.uint8:
            raise ValueError(
                "packed mask bytes require mask_total_bits (the byte "
                "count is 8x short and would truncate the mask)"
            )
        mask_total_bits = int(mask_flat.size)
    if mask_flat.dtype == np.uint8:
        native_form = mask_flat.reshape(-1)
        bits = np.unpackbits(
            native_form, bitorder="little"
        )[:mask_total_bits].astype(bool)
    else:
        bits = np.asarray(mask_flat, bool).reshape(-1)
        native_form = bits
    offsets = np.concatenate(
        [[0], np.cumsum(
            (qo_indptr[1:] - qo_indptr[:-1]).astype(np.int64)
            * np.asarray(kv_lens, np.int64)
        )]
    )
    return bits, native_form, int(mask_total_bits), offsets


def build_prefill_work_units(
    qo_indptr: np.ndarray,  # [B+1] token offsets
    kv_page_indptr: np.ndarray,  # [B+1] page offsets
    kv_page_indices: np.ndarray,
    kv_lens: np.ndarray,  # [B] kv token lengths
    block_q: int,
    pages_per_chunk: int,
    page_size: int,
    mask_flat: Optional[np.ndarray] = None,  # concat per-request [qo*kv]:
    #   bool bits, or uint8 LSB-first packed bytes (+ mask_total_bits)
    mask_total_bits: Optional[int] = None,
    *,
    causal: bool = True,
    window_left: int = -1,
    pack_tiles: bool = True,
    prune: bool = True,
    num_units_pad: Optional[int] = None,
    fused_ingest=None,
):
    """Host-side plan: flatten (qo-tile, request, kv-chunk) work units.

    Returns a dict of numpy arrays padded to a power-of-two unit count
    (pad units: ``first=0, wout=0``, an empty row span: they write and
    corrupt nothing and cost the host nothing), plus the statics the
    arrays were built for and ``stats`` (units before/after pruning,
    ``units_fallback``, row/MXU-cell fill).  A span with nothing left to
    attend (all chunks pruned, ``kv_len == 0``) gets a zero-write
    FALLBACK unit only where its packed tile would else hold no unit.

    ``num_units_pad`` overrides the power-of-two padding with an exact
    unit count (>= the real units, else ValueError): callers that
    re-plan every step against ONE compiled launch — the serving
    engine's rung ladder (serve/engine_kernels.py) — pad every plan of
    a rung to the same cap so the plan-array SHAPES never retrace while
    the values change freely.  Such a plan must hold at least one real
    unit (AssertionError otherwise): its launch runs ``n_units`` grid
    steps, and an empty grid would leave the output unwritten.

    ``n_units`` (an int32 scalar, ``stats["units"]``, or 1 for a plan
    of no unit at all: one identity pad unit) is the count of real
    units, which lead the arrays.  :func:`fused_paged_prefill` takes it
    as the trip count of its unit axis when the plan handed to it
    carries it, so the pad units cost nothing on the device.

    Per-unit fields: ``qstart`` (q-tile token start), ``rowlo``/``rowhi``
    (this unit's request's row span within the tile), ``qpos0``
    (absolute q position of tile row 0 for that request, may be
    negative), ``kvstart``/``kvlen``, ``first`` (first unit of its tile:
    accumulator reset + q-DMA wait), ``wout`` (last unit of its tile:
    output write-back), ``qslot`` (q double-buffer slot, tile parity),
    ``code`` (CODE_FULL / CODE_PARTIAL / CODE_PARTIAL_MASK — the
    plan-time mask descriptor), ``pages``.

    ``causal``/``window_left`` feed the plan-time pruning and FULL
    classification and must match the kernel call (the wrapper passes
    both from the same plan).  A custom mask replaces causal (the
    reference MaskMode::CUSTOM rule); window still ANDs in.

    With ``mask_flat`` (the reference's flat per-request mask concat,
    prefill.py:1492), each unit additionally gets its window of the mask
    re-packed as a little-endian byte bitmap ``mask_bytes [num_units,
    block_q, mask_lane_bytes(chunk)]``, shaped for a direct per-unit
    VMEM fetch; the kernel expands bits in-register (selector dot +
    shifts), so no dense [qo, kv] array ever exists on device (reference
    analogue: packed_custom_mask consumed inside the kernel,
    prefill.cuh:2682).  All-ones windows are demoted to CODE_PARTIAL
    (no expansion) and all-zero windows are pruned, so the expansion
    dot only runs where the mask actually cuts, and only those
    ``CODE_PARTIAL_MASK`` units get a bitmap: every other row of
    ``mask_bytes`` is zero and never touched.  The per-unit re-pack is
    the hottest host-plan loop; when the unit enumeration is canonical
    (``pack_tiles=False`` or every qo_len a multiple of ``block_q``) it
    runs in the C++ planner (csrc/planner.cpp prefill_mask_plan) and the
    per-unit bitmaps are row-selected from its output after pruning.

    ``fused_ingest`` (keyword-only) switches the plan into INGEST mode
    for :func:`fused_paged_prefill_ingest`: the kernel streams RAW
    pre-RoPE K/V rows (contiguous per request on one flat axis) instead
    of cache pages, rotates + quantizes them in-register, and writes the
    finished pages back to the paged cache from the same launch.  Three
    extra per-unit arrays are emitted:

    - ``kvbase`` — flat raw-KV row of the unit's request's kv position
      0 (default: the running cumsum of ``kv_lens``; callers whose raw
      rows live elsewhere on the axis — the engine's rung-padded flat
      token axis — override via ``fused_ingest={"kv_bases": ...}``);
    - ``posoff`` — per-request GLOBAL position offset added to the
      plan-local q/kv positions for the in-kernel RoPE (0 for a
      from-scratch prefill; the engine passes the cascade ``split``,
      the append reroute the first append position);
    - ``wkv`` — 1 on the single unit that owns each (request, chunk)'s
      quantize-append write-back (the FIRST unit touching the chunk in
      stream order, so the rotated values are written exactly once).

    Chunks that attention pruned from EVERY tile (sliding-window /
    all-zero-mask chunks) still must reach the cache: they come back as
    ``CODE_WRITE_ONLY`` units (empty row span, no MXU work, prepended
    ahead of the qstart-ordered stream with ``first=wout=0`` so they
    disturb neither the q pipeline nor the tile parity).

    ``fused_ingest`` accepts ``True`` (defaults for both arrays) or a
    mapping with optional ``"pos_offsets"`` / ``"kv_bases"`` ([B] int
    arrays)."""
    chunk_tokens = pages_per_chunk * page_size
    ingest = bool(fused_ingest) if not isinstance(fused_ingest, dict) \
        else True
    if ingest:
        opts = fused_ingest if isinstance(fused_ingest, dict) else {}
        nB = len(qo_indptr) - 1
        pos_offsets = np.asarray(
            opts.get("pos_offsets")
            if opts.get("pos_offsets") is not None else np.zeros(nB),
            np.int64)
        kv_bases = np.asarray(
            opts.get("kv_bases")
            if opts.get("kv_bases") is not None
            else np.concatenate(
                [[0], np.cumsum(np.asarray(kv_lens, np.int64))])[:-1],
            np.int64)
    if mask_flat is not None:
        causal = False  # MaskMode::CUSTOM replaces causal (window ANDs)
        mask_bits, mask_native, mask_total_bits, mask_offsets = \
            _normalize_mask(mask_flat, mask_total_bits, qo_indptr, kv_lens)
    B = len(qo_indptr) - 1
    qo_lens = [int(qo_indptr[r + 1]) - int(qo_indptr[r]) for r in range(B)]
    # canonical enumeration (per-request tiles) == packed enumeration iff
    # every request's qo span tiles without crossing a block_q boundary
    aligned = all(
        int(qo_indptr[r]) % block_q == 0 for r in range(B) if qo_lens[r] > 0
    )
    packed = pack_tiles and not aligned

    # ---- enumerate (tile, request) row spans ----------------------------
    # span: (tile_start, rowlo, rowhi, request)
    spans = []
    for r in range(B):
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        if qe <= qs:
            continue
        if packed:
            t0, t1 = qs // block_q, (qe - 1) // block_q
            for t in range(t0, t1 + 1):
                ts = t * block_q
                spans.append((ts, max(qs - ts, 0),
                              min(qe - ts, block_q), r))
        else:
            for t in range(cdiv(qe - qs, block_q)):
                ts = qs + t * block_q
                spans.append((ts, 0, min(block_q, qe - ts), r))
    spans.sort(key=lambda s: (s[0], s[3]))

    # ---- classify + prune (canonical index kept for the native-mask
    #      row selection) ---------------------------------------------------
    # unit: [qstart, rowlo, rowhi, qpos0, kvstart, kvlen, code, kv chunk
    #        (-1: none), tile_key, canon_idx, request]
    units = []
    canon_idx = 0
    n_pruned = 0
    wl = int(window_left)
    wkv = []  # ingest: 1 on the unit owning each chunk's write-back
    covered = set()  # ingest: (request, chunk) pairs some kept unit owns
    for ts, rowlo, rowhi, r in spans:
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        kv_len = int(kv_lens[r])
        qpos0 = kv_len - (qe - qs) + (ts - qs)
        n_chunks = max(cdiv(kv_len, chunk_tokens), 1) if kv_len > 0 else 1
        if mask_flat is not None and kv_len > 0:
            req_mask = mask_bits[
                mask_offsets[r] : mask_offsets[r + 1]
            ].reshape(qe - qs, kv_len)
        else:
            req_mask = None
        kept_any = False
        for c in range(n_chunks):
            kvstart = c * chunk_tokens
            ci = canon_idx
            canon_idx += 1
            w = min(chunk_tokens, kv_len - kvstart)
            qp_first = qpos0 + rowlo
            qp_last = qpos0 + rowhi - 1
            # ---- provably-all-masked? -> prune (the hoisted skip) ----
            skip = w <= 0
            if causal and not skip:
                skip = kvstart > qp_last
            if wl >= 0 and not skip:
                skip = kvstart + w - 1 < qp_first - wl
            sub = None
            if req_mask is not None and not skip:
                sub = req_mask[ts + rowlo - qs : ts + rowhi - qs,
                               kvstart : kvstart + w]
                skip = not bool(sub.any())
            if skip and prune:
                n_pruned += 1
                continue
            # ---- provably-full? -> CODE_FULL (no in-kernel masking) ----
            full = (rowlo == 0 and rowhi == block_q and w == chunk_tokens)
            if full and causal:
                full = kvstart + w - 1 <= qp_first
            if full and wl >= 0:
                full = kvstart >= qp_last - wl
            if full and sub is not None:
                full = bool(sub.all())
            if full:
                code = CODE_FULL
            elif sub is not None and not bool(sub.all()):
                code = CODE_PARTIAL_MASK
            else:
                code = CODE_PARTIAL
            units.append([ts, rowlo, rowhi, qpos0, kvstart, kv_len, code,
                          c, ts if packed else (ts, r), ci, r])
            if ingest and (r, c) not in covered:
                covered.add((r, c))
                wkv.append(1)
            else:
                wkv.append(0)
            kept_any = True
        if not kept_any:
            # every chunk pruned (e.g. kv_len == 0): a zero-write fallback unit
            units.append([ts, rowlo, rowlo, qpos0, 0, 0, CODE_PARTIAL,
                          -1, ts if packed else (ts, r), -1, r])
            wkv.append(0)
    if packed:  # a tile's first unit resets it, its last writes it: keep one
        units, wkv = _one_fallback_a_tile(units, wkv)

    # ---- first/wout flags + q slots per tile -----------------------------
    first = [0] * len(units)
    wout = [0] * len(units)
    qslot = [0] * len(units)
    tile_ord = -1
    prev_key = object()
    for i, u in enumerate(units):
        if u[8] != prev_key:
            tile_ord += 1
            first[i] = 1
            if i > 0:
                wout[i - 1] = 1
            prev_key = u[8]
        qslot[i] = tile_ord % 2
    if units:
        wout[-1] = 1

    # ---- ingest: write-only units for chunks attention never kept ----
    # (window / custom-mask pruning can drop a chunk from EVERY tile;
    # its raw K/V still must reach the cache).  Prepended AFTER the
    # flag pass with first=wout=0 so they fetch no q, write no output,
    # and leave the tile parity untouched; qstart <= the first real
    # unit's keeps the ascending-order invariant.
    n_write_only = 0
    if ingest:
        wo_units = []
        for r in range(B):
            kv_len = int(kv_lens[r])
            if kv_len <= 0:
                continue
            for c in range(cdiv(kv_len, chunk_tokens)):
                if (r, c) in covered:
                    continue
                wo_units.append(
                    [units[0][0] if units else 0, 0, 0, 0,
                     c * chunk_tokens, kv_len, CODE_WRITE_ONLY, c,
                     None, -1, r])
        n_write_only = len(wo_units)
        if wo_units:
            units = wo_units + units
            first = [0] * n_write_only + first
            wout = [0] * n_write_only + wout
            qslot = [0] * n_write_only + qslot
            wkv = [1] * n_write_only + wkv

    # the (unpacked) partial-tile write-back rewrite depends on ascending
    # qstart order; packed tiles are disjoint but keep the same ordering
    starts = [u[0] for u in units]
    assert starts == sorted(starts), "work units must be qstart-ordered"

    n_real = len(units)
    if num_units_pad is not None:
        if n_real > num_units_pad:
            raise ValueError(
                f"num_units_pad={num_units_pad} but the plan needs "
                f"{n_real} work units — the caller's per-rung unit cap "
                "is undersized (serve/engine_kernels.py computes it "
                "from the rung statics; a schedule can never exceed it)")
        assert n_real >= 1, (
            "a plan padded to a fixed unit count needs a real unit: the "
            "caller covers every q row with a segment, so even a step "
            "with no KV keeps a zero-write fallback unit a tile")
        U = int(num_units_pad)
    else:
        U = max(next_power_of_two(max(n_real, 1)), 8)
    n_mxu = n_real - n_write_only  # write-only units run no MXU dot
    # the real units as columns; everything past them is the pad unit
    head = np.asarray([u[:8] for u in units], np.int64).reshape(n_real, 8)
    req = np.asarray([u[10] for u in units], np.int64)
    rows_valid = head[:, 2] - head[:, 1]
    stats = {
        "units": n_real,
        "units_canonical": canon_idx,
        "units_pruned": n_pruned,
        "units_fallback": int((head[:, 7] < 0).sum()),
        "tiles": tile_ord + 1,
        "packed": bool(packed),
        "unit_rows_total": n_mxu * block_q,
        "unit_rows_valid": int(rows_valid.sum()),
        "mxu_cells_total": n_mxu * block_q * chunk_tokens,
        "mxu_cells_valid": int((rows_valid * np.clip(
            head[:, 5] - head[:, 4], 0, chunk_tokens)).sum()),
    }
    if ingest:
        stats["ingest_write_only_units"] = n_write_only
        # chunks the ingest launch writes back (== the append traffic
        # the cost model prices): one owner unit per (request, chunk)
        stats["ingest_chunks"] = int(sum(wkv))
        stats["ingest_kv_aligned"] = ingest_rows_aligned(
            kv_bases[req], chunk_tokens)

    # pad units are what zeros say: first=0 (no q fetch/wait), wout=0
    # (never write), empty row span + kvlen 0 (identity online-softmax
    # steps), page 0; only their code is not 0
    def col(values, fill=0):
        out = np.full(U, fill, np.int32)
        out[:n_real] = values
        return out

    # a unit's pages: chunk ``c`` of its request's page run, zeros past
    # the run's end (and for a fallback unit, whose chunk is -1)
    page_indptr = np.asarray(kv_page_indptr, np.int64)
    p0 = page_indptr[req] + np.maximum(head[:, 7], 0) * pages_per_chunk
    p1 = np.where(head[:, 7] >= 0, page_indptr[req + 1], p0)
    idx = p0[:, None] + np.arange(pages_per_chunk)[None, :]
    held = idx < p1[:, None]
    pages = np.zeros((U, pages_per_chunk), np.int32)
    if held.any():
        pages[:n_real] = np.where(
            held, np.asarray(kv_page_indices)[np.where(held, idx, 0)], 0)
    plan = dict(
        qstart=col(head[:, 0]), rowlo=col(head[:, 1]),
        rowhi=col(head[:, 2]), qpos0=col(head[:, 3]),
        kvstart=col(head[:, 4]), kvlen=col(head[:, 5]),
        first=col(first), wout=col(wout), qslot=col(qslot),
        code=col(head[:, 6], CODE_PARTIAL),
        pages=pages.reshape(-1),
        n_units=np.int32(max(n_real, 1)),
        num_units=U,
        block_q=block_q,
        pages_per_chunk=pages_per_chunk,
        stats=stats,
    )
    if ingest:
        # per-unit raw-row base + global-position offset (pad units
        # read harmless row 0 / offset 0)
        plan["kvbase"] = col(kv_bases[req])
        plan["posoff"] = col(pos_offsets[req])
        plan["wkv"] = col(wkv)
    if mask_flat is not None:
        plan["mask_bytes"] = _build_unit_masks(
            units, U, qo_indptr, kv_lens, mask_bits, mask_native,
            mask_total_bits, mask_offsets, block_q, chunk_tokens, packed,
            canon_idx,
        )
    return plan


def _build_unit_masks(units, U, qo_indptr, kv_lens, mask_bits, mask_native,
                      mask_total_bits, mask_offsets, block_q, chunk_tokens,
                      packed, n_canonical):
    """Per-unit packed bitmaps [U, block_q, mask_lane_bytes]: the window
    of the mask of each ``CODE_PARTIAL_MASK`` unit, the only units whose
    bitmap the kernel expands; every other row stays zero (and, the
    array being ``np.zeros``, is never touched).  A plan with no such
    unit gets a read-only view of ONE zero byte in that shape: nobody
    reads it, and at the serving engine's caps the allocation alone
    (8 MB a step, cleared by the allocator once it reuses the block) was
    most of a step's level-0 planning.

    Canonical enumeration -> the C++ planner builds bitmaps for ALL
    canonical units in one pass and the mask units row-select from it
    (pruning removes whole units, never rewrites a bitmap); packed
    enumeration (tile rows offset into the request) -> numpy per-tile
    extraction."""
    from flashinfer_tpu import native

    mb = mask_lane_bytes(chunk_tokens)
    masked = [(i, u) for i, u in enumerate(units)
              if u[6] == CODE_PARTIAL_MASK]
    if not masked:
        return np.broadcast_to(np.uint8(0), (U, block_q, mb))
    out = np.zeros((U, block_q, mb), np.uint8)
    if not packed and native.get_lib() is not None:
        # mask_native is the caller's ORIGINAL packed-bytes form when one
        # was supplied — the C++ planner reads LSB-first bytes directly,
        # so the bool view never round-trips through packbits here
        canon = native.prefill_mask_plan(
            mask_native, mask_total_bits,
            qo_indptr, np.asarray(kv_lens, np.int64),
            block_q, chunk_tokens, mb, max(n_canonical, 1),
        )
        for i, u in masked:
            out[i] = canon[u[9]]
        return out
    for i, u in masked:
        ts, rowlo, rowhi, _qpos0, kvstart, kv_len = u[:6]
        r = u[10]
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        req = mask_bits[mask_offsets[r] : mask_offsets[r + 1]].reshape(
            qe - qs, kv_len
        )
        w = min(chunk_tokens, kv_len - kvstart)
        tile = np.zeros((block_q, chunk_tokens), bool)
        tile[rowlo:rowhi, :w] = req[
            ts + rowlo - qs : ts + rowhi - qs, kvstart : kvstart + w
        ]
        packed_tile = np.packbits(tile, axis=-1, bitorder="little")
        out[i, :, : packed_tile.shape[-1]] = packed_tile
    return out


def build_prefill_ingest_units(
    qo_indptr: np.ndarray,
    kv_page_indptr: np.ndarray,
    kv_page_indices: np.ndarray,
    kv_lens: np.ndarray,
    block_q: int,
    pages_per_chunk: int,
    page_size: int,
    mask_flat: Optional[np.ndarray] = None,
    mask_total_bits: Optional[int] = None,
    *,
    causal: bool = True,
    window_left: int = -1,
    pack_tiles: bool = True,
    prune: bool = True,
    num_units_pad: Optional[int] = None,
    fused_ingest=True,
):
    """The ingest-mode planner entry (the L007 ``PLANNER_KERNELS`` name
    for :func:`_fused_prefill_ingest_kernel`): the same work-unit plan
    machinery as :func:`build_prefill_work_units` with ``fused_ingest``
    forced on, re-emitted as one explicit dict so the analyzer's
    consumed-keys-vs-emitted-keys contract stays statically decidable
    against THIS function (docs/static_analysis.md L007)."""
    base = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices, kv_lens,
        block_q, pages_per_chunk, page_size, mask_flat, mask_total_bits,
        causal=causal, window_left=window_left, pack_tiles=pack_tiles,
        prune=prune, num_units_pad=num_units_pad,
        fused_ingest=fused_ingest,
    )
    plan = dict(
        qstart=base["qstart"], rowlo=base["rowlo"], rowhi=base["rowhi"],
        qpos0=base["qpos0"], kvstart=base["kvstart"], kvlen=base["kvlen"],
        first=base["first"], wout=base["wout"], qslot=base["qslot"],
        code=base["code"], pages=base["pages"], kvbase=base["kvbase"],
        posoff=base["posoff"], wkv=base["wkv"],
        num_units=base["num_units"], block_q=base["block_q"],
        pages_per_chunk=base["pages_per_chunk"], stats=base["stats"],
    )
    if "mask_bytes" in base:
        plan["mask_bytes"] = base["mask_bytes"]
    return plan


def ingest_pages_per_chunk(page_size: int) -> int:
    """The ~512-KV-row DMA chunk recipe every fused-ingest adopter
    shares (``EngineKernelGeom.build``, ``MixedServingStep.plan``, the
    rope reroute) — ONE place to retune the chunk width so the three
    launch sites can never drift onto different tile geometry for the
    same hardware."""
    return max(1, min(512 // int(page_size), 16))


def ingest_block_q(max_rows: int) -> int:
    """The qo-tile recipe shared with :func:`ingest_pages_per_chunk`:
    a pow2 tile, no wider than 128 or the qo axis."""
    from flashinfer_tpu.utils import next_power_of_two

    return min(128, next_power_of_two(max(int(max_rows), 1)))


def _fused_prefill_ingest_kernel(
    # scalar prefetch (the ingest plan: the 11 base arrays + kvbase /
    # posoff / wkv — see build_prefill_work_units(fused_ingest=...))
    qstart_ref, rowlo_ref, rowhi_ref, qpos0_ref, kvstart_ref, kvlen_ref,
    first_ref, wout_ref, qslot_ref, code_ref, pages_ref, kvbase_ref,
    posoff_ref, wkv_ref,
    *refs,
    bq: int,
    ppc: int,
    page_size: int,
    group: int,
    head_dim: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    causal: bool,
    num_units: int,
    has_mask: bool,
    return_lse: bool,
    attend: bool,
    rope_scale: float,
    rope_theta: float,
    rope_interleave: bool,
    kv_quant: str,
    k_scale: float,
    v_scale: float,
    kv_aligned: bool,
):
    """The fused-INGEST work-unit mainloop (ISSUE 14 tentpole): the same
    pipelined online-softmax walk as :func:`_fused_prefill_kernel`, but
    K/V stream as RAW pre-RoPE rows from one flat axis (ONE contiguous
    DMA per chunk — raw rows are request-contiguous, no page gather on
    the read side), RoPE is applied in-register (q at its plan row
    provenance ``posoff + qpos0 + row``, each KV chunk at its global
    positions ``posoff + kvstart + col`` — bitwise the XLA
    ``rotate_at_positions`` math), K/V quantize to the cache storage
    dtype with exactly the quant-append formulas
    (``append_paged_kv_cache_quant_{int8,fp8}``; passthrough caches cast
    bit-untouched), and each chunk's finished pages DMA OUT to the paged
    cache from its single ``wkv`` owner unit — so prefill's KV cache
    traffic is one raw read + one quantized-page write, with attention
    consuming the in-register values instead of re-reading HBM.

    Attention consumes the QUANTIZED codes (dequant rides the caller's
    scale folding, the decode kernels' contract), so the output is
    bitwise the separate-op composition's on every cache dtype, not
    just within the quant bound.  ``attend=False`` is the append-only
    form (the ``rope_quantize_fp8_append_paged_kv_cache`` reroute): no
    q operand, no softmax, just the rotate-quantize-append stream.

    Write-back granularity is whole pages: rows of a chunk's last
    partially-filled page past ``kvlen`` are written as ZERO codes (a
    deterministic value; the composed append preserves prior bits
    there, but those rows sit beyond the request's sequence and are
    rewritten by any later append before they can be read).

    NOTE for the on-chip session: the in-kernel rotation slices the
    lane dim at ``head_dim // 2`` (and stride-2 for interleave) —
    interpret-proven; Mosaic lane-slice support at 64 needs the first
    hardware run before this kernel leaves the committed tier."""
    i = 0
    q_hbm = refs[0] if attend else None
    i += 1 if attend else 0
    k_hbm, v_hbm = refs[i], refs[i + 1]
    i += 2
    mask_ref = refs[i] if has_mask else None
    i += 1 if has_mask else 0
    i += 2  # aliased k/v cache INPUT refs: unread (writes go to the
    #         aliased outputs; aliasing only preserves untouched pages)
    o_hbm = refs[i] if attend else None
    i += 1 if attend else 0
    kc_out, vc_out = refs[i], refs[i + 1]
    i += 2
    lse_hbm = refs[i] if return_lse else None
    i += 1 if return_lse else 0
    (qbuf, kbuf, vbuf, obuf, acc_ref, m_ref, l_ref, kqbuf, vqbuf,
     qsem, ksem, vsem, osem, kwsem, vwsem, lsebuf, lsesem) = refs[i:]
    hkv = pl.program_id(0)
    u = pl.program_id(1)
    chunk_tokens = ppc * page_size
    bqg = bq * group
    half = head_dim // 2

    # trace-time constant inverse frequencies — the _rope_freqs formula
    # verbatim (so the in-kernel rotation is bitwise rotate_at_positions)
    # on a [1, half] 2-D iota (Mosaic has no 1-D iota, and only an
    # integer one: tpu.iota refuses a float result, so cast after)
    inv = 1.0 / (rope_scale * rope_theta ** (
        2.0 * jax.lax.broadcasted_iota(jnp.int32, (1, half), 1)
        .astype(jnp.float32) / head_dim))

    def _rot(x, pos):
        """RoPE x [rows, head_dim] at integer positions [rows, 1] —
        the _apply_rotary math op for op (f32 compute, cast back)."""
        xf = x.astype(jnp.float32)
        ang = pos.astype(jnp.float32) * inv
        c, s = jnp.cos(ang), jnp.sin(ang)
        if rope_interleave:
            x1, x2 = xf[:, 0::2], xf[:, 1::2]
            rot = jnp.stack(
                [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
            ).reshape(xf.shape)
        else:
            x1, x2 = xf[:, :half], xf[:, half:]
            rot = jnp.concatenate(
                [x1 * c - x2 * s, x2 * c + x1 * s], -1)
        return rot.astype(x.dtype)

    if kv_quant == "int8":
        def _quant(x, scale):  # quantize_symmetric_int8, verbatim
            return jnp.clip(
                jnp.round(x.astype(jnp.float32) / scale), -127, 127
            ).astype(kc_out.dtype)
    elif kv_quant == "fp8":
        _finfo = jnp.finfo(kc_out.dtype)

        def _quant(x, scale):  # append_paged_kv_cache_quant_fp8, verbatim
            return jnp.clip(
                x.astype(jnp.float32) / scale, float(_finfo.min),
                float(_finfo.max)).astype(kc_out.dtype)
    else:
        def _quant(x, scale):  # passthrough: the cache-dtype cast only
            return x.astype(kc_out.dtype)

    def kv_dmas(unit, slot):
        # ONE contiguous DMA per chunk and tensor: raw rows live at
        # [kvbase + kvstart, +chunk) of the flat axis — no page walk
        src = kvbase_ref[unit] + kvstart_ref[unit]
        if kv_aligned:  # the plan's promise; Mosaic cannot prove it
            src = pl.multiple_of(src, INGEST_KV_ALIGN)
        return [
            pltpu.make_async_copy(
                k_hbm.at[hkv, pl.ds(src, chunk_tokens)], kbuf.at[slot],
                ksem.at[slot]),
            pltpu.make_async_copy(
                v_hbm.at[hkv, pl.ds(src, chunk_tokens)], vbuf.at[slot],
                vsem.at[slot]),
        ]

    def q_dma(unit, slot):
        return pltpu.make_async_copy(
            q_hbm.at[hkv, pl.ds(qstart_ref[unit], bq)],
            qbuf.at[slot], qsem.at[slot],
        )

    nxt = jnp.minimum(u + 1, num_units - 1)

    if attend:
        @pl.when(jnp.logical_and(u == 0, first_ref[0] == 1))
        def _():
            q_dma(0, qslot_ref[0]).start()

    @pl.when(u == 0)
    def _():
        for d in kv_dmas(0, 0):
            d.start()

    if attend:
        @pl.when(jnp.logical_and(u + 1 < num_units, first_ref[nxt] == 1))
        def _():
            q_dma(nxt, qslot_ref[nxt]).start()

    @pl.when(u + 1 < num_units)
    def _():
        for d in kv_dmas(nxt, jax.lax.rem(u + 1, 2)):
            d.start()

    slot = jax.lax.rem(u, 2)
    qslot = qslot_ref[u]

    if attend:
        @pl.when(first_ref[u] == 1)
        def _():
            q_dma(u, qslot).wait()
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    for d in kv_dmas(u, slot):
        d.wait()

    # ---- the ingest core: rotate + quantize this chunk in-register ----
    kv_pos = (posoff_ref[u] + kvstart_ref[u]
              + jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, 1), 0))
    krot = _rot(kbuf[slot], kv_pos)
    kq = _quant(krot, k_scale)
    vq = _quant(vbuf[slot], v_scale)

    if attend:
        # per-unit q rotation at absolute positions posoff + qpos0 +
        # row: rows outside [rowlo, rowhi) rotate at a neighbouring
        # request's offset but contribute only masked identity steps
        # (CODE_FULL tiles span one request, so every row is correct);
        # recomputing per chunk instead of once per tile keeps the plan
        # at 14 scalars and the VPU work fully DMA-overlapped
        rows_q = jax.lax.broadcasted_iota(jnp.int32, (bqg, 1), 0) // group
        qm = qbuf[qslot].reshape(bqg, head_dim)
        qrot = _rot(qm, posoff_ref[u] + qpos0_ref[u] + rows_q)
        kd = kq if kq.dtype == qrot.dtype else kq.astype(qrot.dtype)
        vd = vq if vq.dtype == qrot.dtype else vq.astype(qrot.dtype)
        s = jax.lax.dot_general(
            qrot, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if logits_soft_cap > 0.0:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)

        def online_update(valid):
            s_ = s if valid is None else jnp.where(valid, s, _NEG_INF)
            m_prev = m_ref[...][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1,
                                                keepdims=True))
            p = jnp.exp(s_ - m_new)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_ref[...][:, :1] + jnp.sum(p, -1, keepdims=True),
                (bqg, 128),
            )
            pv = jax.lax.dot_general(
                p.astype(vd.dtype), vd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, (bqg, 128))

        def bounds_valid():
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk_tokens), 1)
            q_pos = qpos0_ref[u] + rows_q
            kv_po = kvstart_ref[u] + cols
            valid = (
                (rows_q >= rowlo_ref[u]) & (rows_q < rowhi_ref[u])
                & (kv_po < kvlen_ref[u])
            )
            if causal:
                valid = valid & (kv_po <= q_pos)
            if window_left >= 0:
                valid = valid & (kv_po >= q_pos - window_left)
            return valid

        def mask_bits():
            mb = mask_ref.shape[-1]
            bytes_f = mask_ref[...].astype(jnp.int32).astype(jnp.float32)
            sel = (
                jax.lax.broadcasted_iota(
                    jnp.int32, (mb, chunk_tokens), 1) // 8
                == jax.lax.broadcasted_iota(
                    jnp.int32, (mb, chunk_tokens), 0)
            ).astype(jnp.float32)
            byte_col = jax.lax.dot_general(
                bytes_f, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            shift = jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk_tokens), 1
            ) % 8
            bit = (byte_col.astype(jnp.int32) >> shift) & 1
            return jnp.broadcast_to(
                (bit > 0).reshape(bq, 1, chunk_tokens),
                (bq, group, chunk_tokens),
            ).reshape(bqg, chunk_tokens)

        code = code_ref[u]

        @pl.when(code == CODE_FULL)
        def _():
            online_update(None)

        if has_mask:
            @pl.when(code == CODE_PARTIAL)
            def _():
                online_update(bounds_valid())

            @pl.when(code == CODE_PARTIAL_MASK)
            def _():
                online_update(bounds_valid() & mask_bits())
        else:
            @pl.when(code == CODE_PARTIAL)
            def _():
                online_update(bounds_valid())

        @pl.when(wout_ref[u] == 1)
        def _():
            l = l_ref[...][:, :1]
            o = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
                obuf.dtype)
            obuf[...] = o.reshape(obuf.shape)
            out_dma = pltpu.make_async_copy(
                obuf, o_hbm.at[hkv, pl.ds(qstart_ref[u], bq)], osem)
            out_dma.start()
            out_dma.wait()
            if return_lse:
                m = m_ref[...][:, :1]
                lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
                lsebuf[...] = jnp.broadcast_to(lse, (bqg, 128)).reshape(
                    lsebuf.shape)
                lse_dma = pltpu.make_async_copy(
                    lsebuf, lse_hbm.at[hkv, pl.ds(qstart_ref[u], bq)],
                    lsesem)
                lse_dma.start()
                lse_dma.wait()

    # ---- the append write-back: this unit owns the chunk's pages ----
    # (exactly one wkv unit per (request, chunk); rows past kvlen in
    # the last partial page write deterministic zero codes)
    @pl.when(wkv_ref[u] == 1)
    def _():
        w = kvlen_ref[u] - kvstart_ref[u]
        keep = jax.lax.broadcasted_iota(
            jnp.int32, (chunk_tokens, 1), 0) < w
        kqbuf[...] = jnp.where(keep, kq, jnp.zeros_like(kq))
        vqbuf[...] = jnp.where(keep, vq, jnp.zeros_like(vq))

        def page_dmas(j):
            page = pages_ref[u * ppc + j]
            dst = pl.ds(j * page_size, page_size)
            return [
                pltpu.make_async_copy(
                    kqbuf.at[dst], kc_out.at[page, hkv], kwsem.at[j]),
                pltpu.make_async_copy(
                    vqbuf.at[dst], vc_out.at[page, hkv], vwsem.at[j]),
            ]

        for j in range(ppc):
            @pl.when(kvstart_ref[u] + j * page_size < kvlen_ref[u])
            def _(j=j):
                for d in page_dmas(j):
                    d.start()
        for j in range(ppc):
            @pl.when(kvstart_ref[u] + j * page_size < kvlen_ref[u])
            def _(j=j):
                for d in page_dmas(j):
                    d.wait()


def _fused_prefill_kernel(
    # scalar prefetch (the plan)
    qstart_ref, rowlo_ref, rowhi_ref, qpos0_ref, kvstart_ref, kvlen_ref,
    first_ref, wout_ref, qslot_ref, code_ref, pages_ref,
    # inputs: q/k/v in ANY (manual DMA); with has_mask, a pipelined
    # per-unit packed-mask block [bq, mask_lane_bytes] uint8 follows
    *refs,
    bq: int,
    ppc: int,
    page_size: int,
    group: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    causal: bool,
    has_mask: bool,
    return_lse: bool,
    trace_events: bool,
):
    i = 3
    q_hbm, k_hbm, v_hbm = refs[0], refs[1], refs[2]
    mask_ref = refs[i] if has_mask else None
    i += 1 if has_mask else 0
    o_hbm = refs[i]
    i += 1
    lse_hbm = refs[i] if return_lse else None
    i += 1 if return_lse else 0
    ev_ref = refs[i] if trace_events else None
    i += 1 if trace_events else 0
    (qbuf, kbuf, vbuf, obuf, acc_ref, m_ref, l_ref,
     qsem, ksem, vsem, osem, lsebuf, lsesem) = refs[i:]
    hkv = pl.program_id(0)
    u = pl.program_id(1)
    chunk_tokens = ppc * page_size

    if trace_events:
        # device-side event tag, reference profiler bit layout
        # (profiler.decode_tag): sm_id <- kv head, block <- work unit,
        # event 0, kInstant; slot order == the sequential grid order, so
        # stream position doubles as the timestamp.  The block shape
        # covers 8 consecutive units (row u % 8) so the buffer costs
        # 512 B per (head, unit) octet instead of 4 KB per step.
        tag = (hkv << 24) | ((u & 0xFFF) << 12) | 2
        ev_ref[pl.ds(jax.lax.rem(u, 8), 1), :] = jnp.full(
            (1, 128), tag, jnp.int32
        )

    def kv_dmas(unit, slot):
        dmas = []
        # wedge-lint: ok default ppc=8 (2 DMAs/page <= 2x queue depth, round-2-validated shape); autotuner candidates guarded; never-compiled kernel stays hw-queue item 3
        for j in range(ppc):
            page = pages_ref[unit * ppc + j]
            dst = pl.ds(j * page_size, page_size)
            dmas.append(pltpu.make_async_copy(
                k_hbm.at[page, hkv], kbuf.at[slot, dst, :], ksem.at[slot, j]))
            dmas.append(pltpu.make_async_copy(
                v_hbm.at[page, hkv], vbuf.at[slot, dst, :], vsem.at[slot, j]))
        return dmas

    def q_dma(unit, slot):
        # all q heads of this kv head's group in one DMA: q is laid out
        # [Hkv, tq, group, D] by the wrapper so the head dim is a full
        # index, not a partial sublane slice (Mosaic requires 8-aligned
        # sublane slices; group can be 4)
        return pltpu.make_async_copy(
            q_hbm.at[hkv, pl.ds(qstart_ref[unit], bq)],
            qbuf.at[slot], qsem.at[slot],
        )

    # the unit axis's trip count (the plan's count of real units, or the
    # static length of its arrays: the launcher's choice).  It guards every
    # prefetch below, so no DMA is started that no later grid step waits for.
    num_units = pl.num_programs(1)
    nxt = jnp.minimum(u + 1, num_units - 1)

    # warm-up: unit 0's q tile (only if unit 0 opens a tile — an
    # all-padding plan must not leave an unwaited DMA) + its KV chunk
    @pl.when(jnp.logical_and(u == 0, first_ref[0] == 1))
    def _():
        q_dma(0, qslot_ref[0]).start()

    @pl.when(u == 0)
    def _():
        for d in kv_dmas(0, 0):
            d.start()

    # pipelined prefetch: next tile's q (issued at this tile's last unit,
    # overlapping this unit's compute) and next unit's KV chunk
    @pl.when(jnp.logical_and(u + 1 < num_units, first_ref[nxt] == 1))
    def _():
        q_dma(nxt, qslot_ref[nxt]).start()

    @pl.when(u + 1 < num_units)
    def _():
        for d in kv_dmas(nxt, jax.lax.rem(u + 1, 2)):
            d.start()

    slot = jax.lax.rem(u, 2)
    qslot = qslot_ref[u]

    @pl.when(first_ref[u] == 1)
    def _():
        q_dma(u, qslot).wait()
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for d in kv_dmas(u, slot):
        d.wait()

    # the whole GQA group rides one MXU dot: merged rows r = q_row*group+g,
    # so the q-row of merged row r is r // group (computed by iota, no
    # relayout), and [bq*group, D] -> [bq, group, D] is a free reshape
    bqg = bq * group
    k = kbuf[slot]
    v = vbuf[slot]
    qm = qbuf[qslot].reshape(bqg, k.shape[-1])  # [bq*group, D]
    if k.dtype != qm.dtype:
        # quantized (int8/fp8) KV cache: bytes cross HBM at the narrow
        # width, dequant is an in-register cast; scalar k_scale/v_scale
        # are folded into sm_scale / the caller's output (the decode
        # kernels' scale-folding contract).  Same-dtype caches take the
        # untouched original path bit-for-bit.
        k = k.astype(qm.dtype)
        v = v.astype(qm.dtype)
    s = jax.lax.dot_general(
        qm, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [bq*group, chunk]
    if logits_soft_cap > 0.0:
        s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)

    def online_update(valid):
        """One online-softmax step; ``valid=None`` is the CODE_FULL fast
        path (no mask materialized, no selects — the MFU path the
        plan-time hoisting exists to reach)."""
        s_ = s if valid is None else jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[...][:, :1] + jnp.sum(p, -1, keepdims=True),
            (bqg, 128),
        )
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, (bqg, 128))

    def bounds_valid():
        rows_q = jax.lax.broadcasted_iota(jnp.int32, (bqg, 1), 0) // group
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, chunk_tokens), 1)
        q_pos = qpos0_ref[u] + rows_q
        kv_pos = kvstart_ref[u] + cols
        valid = (
            (rows_q >= rowlo_ref[u]) & (rows_q < rowhi_ref[u])
            & (kv_pos < kvlen_ref[u])
        )
        if causal:
            valid = valid & (kv_pos <= q_pos)
        if window_left >= 0:
            valid = valid & (kv_pos >= q_pos - window_left)
        return valid

    def mask_bits():
        # expand the packed per-unit bitmap in-register.  Lane-dim
        # byte->column expansion is an unsupported Mosaic shape cast, so
        # it rides a constant selector-matrix MXU dot (byte values <= 255
        # are exact in f32); the bit extract is VPU shifts.
        mb = mask_ref.shape[-1]
        # Mosaic has no direct uint8 -> f32 cast ("Unsupported cast",
        # banked 2026-07-31 hw tier); widen through int32 first
        bytes_f = mask_ref[...].astype(jnp.int32).astype(jnp.float32)
        sel = (
            jax.lax.broadcasted_iota(jnp.int32, (mb, chunk_tokens), 1) // 8
            == jax.lax.broadcasted_iota(jnp.int32, (mb, chunk_tokens), 0)
        ).astype(jnp.float32)
        byte_col = jax.lax.dot_general(
            bytes_f, sel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, chunk]: the byte holding each column's bit
        shift = jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        ) % 8
        bit = (byte_col.astype(jnp.int32) >> shift) & 1  # [bq, chunk]
        # q-row -> merged GQA rows: sublane-side broadcast + free
        # leading-dim reshape (lane dim untouched)
        return jnp.broadcast_to(
            (bit > 0).reshape(bq, 1, chunk_tokens),
            (bq, group, chunk_tokens),
        ).reshape(bqg, chunk_tokens)

    code = code_ref[u]

    @pl.when(code == CODE_FULL)
    def _():
        online_update(None)

    if has_mask:
        @pl.when(code == CODE_PARTIAL)
        def _():
            online_update(bounds_valid())

        @pl.when(code == CODE_PARTIAL_MASK)
        def _():
            online_update(bounds_valid() & mask_bits())
    else:
        @pl.when(code != CODE_FULL)
        def _():
            online_update(bounds_valid())

    @pl.when(wout_ref[u] == 1)
    def _():
        l = l_ref[...][:, :1]
        o = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(obuf.dtype)
        obuf[...] = o.reshape(obuf.shape)
        out_dma = pltpu.make_async_copy(
            obuf,
            o_hbm.at[hkv, pl.ds(qstart_ref[u], bq)],
            osem,
        )
        out_dma.start()
        out_dma.wait()
        if return_lse:
            # per-row log-sum-exp for downstream state merges (cascade
            # composition / split-KV reduction): rows that attended
            # nothing emit the _NEG_INF empty-state sentinel, which
            # merge_state treats as a hard-zero weight
            m = m_ref[...][:, :1]
            lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
            lsebuf[...] = jnp.broadcast_to(lse, (bqg, 128)).reshape(
                lsebuf.shape)
            lse_dma = pltpu.make_async_copy(
                lsebuf,
                lse_hbm.at[hkv, pl.ds(qstart_ref[u], bq)],
                lsesem,
            )
            lse_dma.start()
            lse_dma.wait()


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_units", "block_q", "pages_per_chunk", "sm_scale",
        "logits_soft_cap", "window_left", "causal", "return_lse",
        "trace_events", "name",
    ),
)
def fused_paged_prefill(
    q: jax.Array,  # [tq_pad, H, D] — PRE-PADDED (bucketed) by the caller
    k_cache: jax.Array,  # [pages, Hkv, page_size, D] (HND)
    v_cache: jax.Array,
    plan: dict,  # jnp arrays from build_prefill_work_units
    *,
    num_units: int,  # static length of the plan arrays (pad units included)
    block_q: int = 128,
    pages_per_chunk: int = 8,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    causal: bool = True,
    return_lse: bool = False,
    trace_events: bool = False,
    name: str = "fused_paged_prefill",  # the launch's device-event name
):
    total_q, H, D = q.shape
    _, Hkv, page_size, _ = k_cache.shape
    group = H // Hkv
    chunk_tokens = pages_per_chunk * page_size
    # packed custom mask rides in the plan ([U, bq, mb] from
    # build_prefill_work_units(mask_flat=...)); presence changes the jit
    # pytree structure, so the masked/unmasked variants compile separately
    mask_bytes = plan.get("mask_bytes")
    has_mask = mask_bytes is not None
    if has_mask:
        causal = False  # MaskMode::CUSTOM replaces causal (window still ANDs)
    # extra block so full-bq tile DMAs at the tail stay in bounds; lay q
    # out [Hkv, tq, group, D] so the kernel's per-unit q DMA indexes the
    # kv-head dim instead of slicing a sub-sublane head range
    q_pad = jnp.pad(q, ((0, block_q), (0, 0), (0, 0)))
    q_pad = jnp.transpose(
        q_pad.reshape(total_q + block_q, Hkv, group, D), (1, 0, 2, 3)
    )
    # run only the units the plan has: real units lead the arrays and pad
    # units are identity steps, so the plan's own count, where the caller
    # hands it over, bounds the unit axis (a traced scalar: shapes, and so
    # the trace, stay functions of ``num_units`` alone)
    n_units = plan.get("n_units", num_units)

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if has_mask:
        mb = mask_bytes.shape[-1]
        in_specs.append(
            pl.BlockSpec(
                (None, block_q, mb),
                lambda h, u, *prefetch: (u, 0, 0),
            )
        )
    out_specs = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = jax.ShapeDtypeStruct(
        (Hkv, total_q + block_q, group, D), q.dtype
    )
    if return_lse:
        # lse rides the same manual-DMA write-back as the output (lane
        # dim broadcast to 128 — the decode kernels' lse layout); rows
        # no unit covered keep the zero-init (callers that need the
        # empty-state sentinel cover every row with a plan segment, the
        # engine-planner contract)
        out_specs = [out_specs, pl.BlockSpec(memory_space=pl.ANY)]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (Hkv, total_q + block_q, group, 128), jnp.float32
        )]
    if trace_events:
        # one tag row per grid step (reference profiler.cuh device tag
        # buffer, TPU form: see flashinfer_tpu.profiler module docs);
        # the 12-bit block field of the reference layout caps traceable
        # plans — refuse loudly rather than alias units
        if num_units > 4096:
            raise ValueError(
                "trace_events supports plans up to 4096 work units "
                f"(12-bit tag block field), got {num_units}"
            )
        ev_spec = pl.BlockSpec(
            (None, None, 8, 128), lambda h, u, *prefetch: (h, u // 8, 0, 0)
        )
        ev_shape = jax.ShapeDtypeStruct(
            (Hkv, cdiv(num_units, 8), 8, 128), jnp.int32
        )
        out_specs = (out_specs if isinstance(out_specs, list)
                     else [out_specs]) + [ev_spec]
        out_shape = (out_shape if isinstance(out_shape, list)
                     else [out_shape]) + [ev_shape]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=11,
        grid=(Hkv, n_units),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, block_q, group, D), q.dtype),
            pltpu.VMEM((2, chunk_tokens, D), k_cache.dtype),
            pltpu.VMEM((2, chunk_tokens, D), v_cache.dtype),
            pltpu.VMEM((block_q, group, D), q.dtype),
            pltpu.VMEM((block_q * group, D), jnp.float32),
            pltpu.VMEM((block_q * group, 128), jnp.float32),
            pltpu.VMEM((block_q * group, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2, pages_per_chunk)),
            pltpu.SemaphoreType.DMA((2, pages_per_chunk)),
            pltpu.SemaphoreType.DMA(()),
            # lse write-back staging + its DMA sem.  The ENTRY exists
            # on both paths so the scratch list stays a statically
            # countable literal (the L007 arity / L009 VMEM-evaluator
            # contracts); the SHAPE degenerates to one sublane row
            # when lse is off so non-lse launches reclaim the VMEM
            pltpu.VMEM((block_q, group, 128) if return_lse
                       else (1, 1, 128), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    operands = [q_pad, k_cache, v_cache]
    if has_mask:
        operands.append(mask_bytes)
    out = pl.pallas_call(
        functools.partial(
            _fused_prefill_kernel,
            bq=block_q, ppc=pages_per_chunk, page_size=page_size,
            group=group, sm_scale=sm_scale, logits_soft_cap=logits_soft_cap,
            window_left=window_left, causal=causal,
            has_mask=has_mask, return_lse=return_lse,
            trace_events=trace_events,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            has_side_effects=True,
        ),
        interpret=use_interpret(),
        name=name,
    )(
        plan["qstart"], plan["rowlo"], plan["rowhi"], plan["qpos0"],
        plan["kvstart"], plan["kvlen"], plan["first"], plan["wout"],
        plan["qslot"], plan["code"], plan["pages"],
        *operands,
    )
    lse = None
    if return_lse and trace_events:
        out, lse_raw, ev = out
    elif return_lse:
        out, lse_raw = out
    elif trace_events:
        out, ev = out
    if trace_events:
        # [Hkv, ceil(U/8), 8, 128] -> [Hkv, num_units] tags, grid order;
        # a unit past the trip count was not run and has no tag (0, which
        # the exporter skips), whatever its never-written slot holds
        events = ev[..., 0].reshape(Hkv, -1)[:, :num_units]
        events = jnp.where(jnp.arange(num_units) < n_units, events, 0)
    if return_lse:
        # [Hkv, tq_pad, group, 128] -> [tq, H] (lane 0 carries the value)
        lse = jnp.transpose(lse_raw[:, :total_q, :, 0], (1, 0, 2)).reshape(
            total_q, H
        )
    # [Hkv, tq_pad, group, D] -> [tq, H, D]
    result = jnp.transpose(out[:, :total_q], (1, 0, 2, 3)).reshape(
        total_q, H, D
    )
    ret = (result,) + ((lse,) if return_lse else ())
    ret = ret + ((events,) if trace_events else ())
    return ret if len(ret) > 1 else result


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_units", "block_q", "pages_per_chunk", "sm_scale",
        "logits_soft_cap", "window_left", "causal", "return_lse",
        "attend", "rope_scale", "rope_theta", "rope_interleave",
        "kv_quant", "k_scale", "v_scale", "kv_aligned",
    ),
)
def fused_paged_prefill_ingest(
    q: Optional[jax.Array],  # [tq_pad, H, D] PRE-PADDED; None if attend=False
    k_new: jax.Array,  # [total_kv, Hkv, D] RAW pre-RoPE rows, flat axis
    v_new: jax.Array,  # [total_kv, Hkv, D]
    k_cache: jax.Array,  # [pages, Hkv, page_size, D] (HND) — ALIASED out
    v_cache: jax.Array,
    plan: dict,  # jnp arrays from build_prefill_ingest_units
    *,
    num_units: int,
    block_q: int = 128,
    pages_per_chunk: int = 8,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    causal: bool = True,
    return_lse: bool = False,
    attend: bool = True,
    rope_scale: float = 1.0,
    rope_theta: float = 1e4,
    rope_interleave: bool = False,
    kv_quant: str = "none",  # "none" | "int8" | "fp8"
    k_scale: float = 1.0,  # quant-append scales: high_precision = code * scale
    v_scale: float = 1.0,
    kv_aligned: bool = False,  # the plan's stats["ingest_kv_aligned"]
):
    """Fused prefill INGEST launch: RoPE + KV-quantize-append folded
    into the work-unit prefill mainloop (ISSUE 14 tentpole; the TPU
    analogue of the reference's ``rope_quantize_fp8_append_paged_kv_
    cache`` fused op, rope.py:1504, EXTENDED through attention).

    Consumes RAW pre-RoPE q / k / v; returns the attention output over
    the rotated values AND the updated caches holding exactly the bits
    ``append_paged_kv_cache_quant_{int8,fp8}`` (or a plain cast append)
    would have written — the caches are input/output ALIASED, so under
    caller donation the append happens in place.  ``sm_scale`` is the
    PLAIN softmax scale: the launcher folds ``k_scale`` into it and
    applies ``v_scale`` to the output for quantized caches (the decode
    kernels' scale-folding contract), so callers pass reference
    semantics.  ``attend=False`` is the append-only form: no q, no
    output — returns just the updated ``(k_cache, v_cache)``.

    Rotation covers the FULL head_dim (``rotary_dim == head_dim``);
    partial-rotary callers stay on the separate-op composition.

    ``kv_aligned`` is the plan's ``stats["ingest_kv_aligned"]``: every
    raw-row DMA source starts on :data:`INGEST_KV_ALIGN`.  The compiled
    kernel needs it (see the constant); interpret mode does not."""
    if not kv_aligned and not use_interpret():
        raise ValueError(
            "fused_paged_prefill_ingest: this plan's raw K/V rows do not "
            f"all start on a multiple of {INGEST_KV_ALIGN} rows of the "
            "flat axis (stats['ingest_kv_aligned'] is False), and Mosaic "
            "cannot slice the tiled row axis at an unaligned dynamic "
            "offset — run the composed rope -> append -> attention ops "
            "for this layout")
    total_kv, Hkv, D = k_new.shape
    page_size = k_cache.shape[2]
    chunk_tokens = pages_per_chunk * page_size
    mask_bytes = plan.get("mask_bytes")
    has_mask = mask_bytes is not None
    if has_mask:
        causal = False  # MaskMode::CUSTOM replaces causal (window ANDs)
    # pad raw rows so full-chunk DMAs at the tail stay in bounds, and
    # lay both out [Hkv, tkv, D] so the per-chunk DMA indexes the head
    k_pad = jnp.transpose(
        jnp.pad(k_new, ((0, chunk_tokens), (0, 0), (0, 0))), (1, 0, 2))
    v_pad = jnp.transpose(
        jnp.pad(v_new, ((0, chunk_tokens), (0, 0), (0, 0))), (1, 0, 2))
    if attend:
        total_q, H, _ = q.shape
        group = H // Hkv
        qdtype = q.dtype
        q_op = jnp.transpose(
            jnp.pad(q, ((0, block_q), (0, 0), (0, 0))).reshape(
                total_q + block_q, Hkv, group, D), (1, 0, 2, 3))
    else:
        total_q, group, qdtype, q_op = 0, 1, k_new.dtype, None
    sm_eff = float(sm_scale) * (float(k_scale) if kv_quant != "none"
                                else 1.0)

    in_specs = []
    operands = []
    if attend:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(q_op)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    operands += [k_pad, v_pad]
    if has_mask:
        mb = mask_bytes.shape[-1]
        in_specs.append(pl.BlockSpec(
            (None, block_q, mb), lambda h, u, *prefetch: (u, 0, 0)))
        operands.append(mask_bytes)
    # the aliased cache inputs ride LAST so their flat input indices are
    # a fixed function of the operand list length
    kc_in_idx = 14 + len(in_specs)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    operands += [k_cache, v_cache]

    out_specs = []
    out_shape = []
    if attend:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct(
            (Hkv, total_q + block_q, group, D), qdtype))
    kc_out_idx = len(out_specs)
    out_specs += [pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)]
    out_shape += [
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    if return_lse:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct(
            (Hkv, total_q + block_q, group, 128), jnp.float32))
    aliases = {kc_in_idx: kc_out_idx, kc_in_idx + 1: kc_out_idx + 1}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=14,
        grid=(Hkv, num_units),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, block_q, group, D) if attend else (1, 1, 1, 1),
                       qdtype),
            pltpu.VMEM((2, chunk_tokens, D), k_new.dtype),
            pltpu.VMEM((2, chunk_tokens, D), v_new.dtype),
            pltpu.VMEM((block_q, group, D) if attend else (1, 1, 1),
                       qdtype),
            pltpu.VMEM((block_q * group, D) if attend else (1, 128),
                       jnp.float32),
            pltpu.VMEM((block_q * group, 128) if attend else (1, 128),
                       jnp.float32),
            pltpu.VMEM((block_q * group, 128) if attend else (1, 128),
                       jnp.float32),
            pltpu.VMEM((chunk_tokens, D), k_cache.dtype),
            pltpu.VMEM((chunk_tokens, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((pages_per_chunk,)),
            pltpu.SemaphoreType.DMA((pages_per_chunk,)),
            pltpu.VMEM((block_q, group, 128) if return_lse
                       else (1, 1, 128), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _fused_prefill_ingest_kernel,
            bq=block_q, ppc=pages_per_chunk, page_size=page_size,
            group=group, head_dim=D, sm_scale=sm_eff,
            logits_soft_cap=logits_soft_cap, window_left=window_left,
            causal=causal, num_units=num_units, has_mask=has_mask,
            return_lse=return_lse, attend=attend,
            rope_scale=rope_scale, rope_theta=rope_theta,
            rope_interleave=rope_interleave, kv_quant=kv_quant,
            k_scale=float(k_scale), v_scale=float(v_scale),
            kv_aligned=kv_aligned,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            has_side_effects=True,
        ),
        interpret=use_interpret(),
        input_output_aliases=aliases,
        name="fused_paged_prefill_ingest",  # the device-event name
    )(
        plan["qstart"], plan["rowlo"], plan["rowhi"], plan["qpos0"],
        plan["kvstart"], plan["kvlen"], plan["first"], plan["wout"],
        plan["qslot"], plan["code"], plan["pages"], plan["kvbase"],
        plan["posoff"], plan["wkv"],
        *operands,
    )
    if not attend:
        kc2, vc2 = out
        return kc2, vc2
    if return_lse:
        o_raw, kc2, vc2, lse_raw = out
    else:
        o_raw, kc2, vc2 = out
    result = jnp.transpose(o_raw[:, :total_q], (1, 0, 2, 3)).reshape(
        total_q, H, D)
    if kv_quant != "none":
        # the quantized-cache scale-folding epilogue: v codes attended,
        # real output = codes-output * v_scale (linear in V, so exact)
        result = (result.astype(jnp.float32) * float(v_scale)).astype(
            qdtype)
    if return_lse:
        lse = jnp.transpose(lse_raw[:, :total_q, :, 0], (1, 0, 2)).reshape(
            total_q, H)
        return result, lse, (kc2, vc2)
    return result, (kc2, vc2)


def _one_fallback_a_tile(units, wkv):
    """A packed plan's fallback units (kv chunk -1: a span with nothing
    to attend), cut to those a launch needs: a tile's FIRST unit resets
    its whole ``[block_q x group]`` accumulator and its LAST writes the
    whole tile back, so a tile that holds any other unit needs none (the
    rows no unit covers leave ``o = 0``, ``lse = -inf``, what the
    fallback unit left), and a tile that holds nothing else needs one.
    Unit order is kept; ``wkv`` rides along (a fallback owns no chunk)."""
    busy = {u[8] for u in units if u[7] >= 0}
    keep = []
    for i, u in enumerate(units):
        if u[7] < 0:
            if u[8] in busy:
                continue
            busy.add(u[8])
        keep.append(i)
    return [units[i] for i in keep], [wkv[i] for i in keep]
