"""Slot-addressed Mamba-2 state update: one Pallas launch a layer advances
the state of exactly the slots a serving step schedules, in place.

The engine keeps one layer's recurrent state as ``[slots, heads x d_head,
d_state]`` float32 (``models/granite_hybrid.py``) and a step's plan
(``serve/recurrent.py``) names, per slot, one segment of the flat batch.  The
unit of work here is an ITEM: one decoding lane (a one-token segment) or one
work unit of a longer segment (at most ``chunk`` rows).  Lanes come first,
compacted; the plan's units follow in the plan's order.

- grid ``(head block, item)``, items innermost and sequential; the item axis
  runs the step's real items only (a traced bound);
- the state operand is aliased to the output and blocked ``(slot[item], head
  block)`` for both: a slot no item names is never read, written or copied,
  and consecutive units of one segment map to the same block, which stays in
  VMEM from the segment's first unit to its last and is written back once;
- a lane takes the one-row path, float32 throughout: ``S <- exp(D A) S + D
  (x (x) B)``, ``y = S C``.  The lanes' ``x`` rows arrive TRANSPOSED and
  compacted, ``[heads x d_head, lanes]``, so that one matrix product with a
  one-hot row of ``B`` leaves the outer product laid out as the state is;
  ``exp(D A)`` and ``D`` are scalars a lane and head;
- a unit takes the chunked (SSD) path of ``_ssd_chunk_kernel``
  (``ops/mamba_kernel.py``, whose score rule and transposes it calls) over
  its head block, as many heads to a matrix product as fill 128 columns:
  the decay masked inside the exponent, the exponents' cumulative sums and
  transposes by matrix products at ``Precision.HIGHEST`` (a default pass
  rounds them before the ``exp``), rows past the unit's count dead (decay 1,
  nothing added, nothing stored);
- the rows in and out (``x``, ``B``, ``C``, ``D`` and ``y``) are whole-batch
  blocks resident per head block; a unit reads its window of them at a
  dynamic row offset.

``y`` excludes the ``D x`` skip term; the caller adds it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.ops.mamba_kernel import causal_scores, transposed
from flashinfer_tpu.utils import use_interpret

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_FIRST, _FRESH, _LANE = 1, 2, 4   # an item's flags
_LANE_COLS = 128          # the lanes' compact axis is padded to whole tiles
_BLOCK_BYTES = 512 * 1024  # of state a grid step moves, at least ...
_BLOCK_TILES = 16          # ... in at most this many 128-column tiles of heads


def head_block(heads: int, d_head: int, d_state: int, rep: int) -> int:
    """Heads of one grid step: the fewest that move ``_BLOCK_BYTES`` of
    state or fill ``_BLOCK_TILES`` tiles (the units' path unrolls over a
    block's tiles), else the most there are, within one B/C group (``rep``
    heads), their columns whole 128-lane tiles (or all the heads)."""
    fits = [hb for hb in range(1, rep + 1) if rep % hb == 0
            and ((hb * d_head) % 128 == 0 or hb == heads)]
    if not fits:
        raise ValueError(
            f"ssm_slot_update: no block of whole 128-column tiles inside a "
            f"B/C group of {rep} heads x {d_head}")
    for hb in fits:
        if (hb * d_head * d_state * 4 >= _BLOCK_BYTES
                or hb * d_head >= _BLOCK_TILES * 128):
            return hb
    return fits[-1]


def plan_items(seg, work, n_work):
    """The step's items from its plan, on the device: ``(slot, row0, n,
    flags)`` int32 ``[max_batch + units]`` each, the count of real items (at
    least 1: an item past the count leaves slot 0 as it is), the count of
    lanes and each lane's row."""
    slots, units = seg.shape[0], work.shape[0]
    one = seg[:, 1] == 1
    n_lanes = jnp.sum(one, dtype=jnp.int32)
    lane_slot = jnp.nonzero(one, size=slots, fill_value=0)[0].astype(jnp.int32)
    lane_row = seg[lane_slot, 0]
    i = jnp.arange(slots + units, dtype=jnp.int32)
    is_lane = i < n_lanes
    real = i < n_lanes + n_work
    li = jnp.minimum(i, slots - 1)
    w = work[jnp.clip(i - n_lanes, 0, units - 1)]
    # a unit's `last` is not carried: a slot's block is written back when
    # the next item names another
    unit_flags = _FIRST * (w[:, 3] > 0) + _FRESH * (w[:, 5] > 0)
    lane_flags = _LANE + _FIRST + _FRESH * (seg[lane_slot[li], 2] > 0)
    pick = lambda lane, unit, pad: jnp.where(   # noqa: E731
        is_lane, lane, jnp.where(real, unit, pad)).astype(jnp.int32)
    items = (pick(lane_slot[li], w[:, 2], 0), pick(lane_row[li], w[:, 0], 0),
             pick(1, w[:, 1], 0), pick(lane_flags, unit_flags, _FIRST))
    return items, jnp.maximum(n_lanes + n_work, 1), n_lanes, lane_row


def _window(q: int) -> int:
    """Rows of the aligned window that holds any ``q`` consecutive rows."""
    return -(-q // 8) * 8 + 8


def _load_rows(ref, r0, q, cols=slice(None)):
    """``ref[r0:r0 + q, cols]`` for any ``r0``: Mosaic reads a dynamic row
    offset only at a multiple of 8, so the aligned window around the rows
    is read and rotated up."""
    base = pl.multiple_of((r0 // 8) * 8, 8)
    rows = _window(q)
    return pltpu.roll(ref[pl.ds(base, rows), cols], rows - (r0 - base),
                      0)[:q]


def _store_rows(ref, r0, n, cols, val):
    """``ref[r0:r0 + n, cols] = val[:n]``, the other rows left as they are:
    the aligned window is read, ``val`` rotated down into place under a
    mask, and the window written back."""
    q = val.shape[0]
    base = pl.multiple_of((r0 // 8) * 8, 8)
    rows, off = _window(q), r0 - base
    moved = pltpu.roll(jnp.pad(val, ((0, rows - q), (0, 0))), off, 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    ref[pl.ds(base, rows), cols] = jnp.where(
        (at >= off) & (at < off + n), moved, ref[pl.ds(base, rows), cols])


def _kernel(slot_ref, row0_ref, n_ref, flag_ref,        # scalar prefetch
            s_ref, x_ref, b_ref, c_ref, dl_ref, a_ref,  # the state, the rows
            xt_ref, bl_ref, cl_ref, al_ref, dll_ref,    # the lanes
            so_ref, y_ref, yt_ref, xb_ref, *,
            q: int, hb: int, dh: int, pack: int):
    del slot_ref
    i = pl.program_id(1)
    h0 = pl.program_id(0) * hb
    flags = flag_ref[i]
    first, fresh = (flags & _FIRST) > 0, (flags & _FRESH) > 0
    is_lane = (flags & _LANE) > 0
    ds = s_ref.shape[-1]
    w = pack * dh                       # columns of one tile of heads

    @pl.when(i == 0)
    def _clear():
        y_ref[...] = jnp.zeros_like(y_ref)
        yt_ref[...] = jnp.zeros_like(yt_ref)

    @pl.when(is_lane)
    def _lane():
        # the lane's index among the lanes is its item index
        cols = xt_ref.shape[1]
        brow = bl_ref[pl.ds(i, 1), :]
        crow = cl_ref[pl.ds(i, 1), :]
        hot = jax.lax.broadcasted_iota(jnp.int32, (cols, ds), 0) == i
        # x (x) B: column i of the transposed rows times B's row.  Both are
        # the activations' dtype, so where that is bfloat16 one pass of the
        # matrix unit gives the products exactly; float32 rows take HIGHEST
        # graft-lint: ok float32 -> the rows' bfloat16 or float32; compiled for the chip in tests/test_mosaic_compile.py
        onehot_b = jnp.where(hot, jnp.broadcast_to(brow, (cols, ds)),
                             0.0).astype(xt_ref.dtype)
        xb_ref[...] = jax.lax.dot_general(
            xt_ref[...], onehot_b,
            (((1,), (0,)), ((), ())), preferred_element_type=_F32,
            precision=_HI if xt_ref.dtype == _F32 else None)
        here = jax.lax.broadcasted_iota(jnp.int32, (dh, cols), 1) == i

        def head(h, carry):
            rows = pl.ds(pl.multiple_of(h * dh, dh), dh)
            s = jnp.where(fresh, 0.0, s_ref[rows, :])
            s = al_ref[i, h0 + h] * s + dll_ref[i, h0 + h] * xb_ref[rows, :]
            so_ref[rows, :] = s
            y1 = jnp.sum(s * crow, axis=-1, keepdims=True)
            yt_ref[rows, :] = jnp.where(here, y1, yt_ref[rows, :])
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(jnp.logical_not(is_lane))
    def _unit():
        r0, n = row0_ref[i], n_ref[i]
        live = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) < n
        bf, cf = _load_rows(b_ref, r0, q), _load_rows(c_ref, r0, q)
        dl = jnp.where(live, _load_rows(dl_ref, r0, q), 0.0)  # [q, hb]
        da = dl * a_ref[...]
        tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)).astype(_F32)
        dot = functools.partial(jax.lax.dot_general,
                                preferred_element_type=_F32)
        # the cumulative log-decay of every row and head, by a triangular
        # product: HIGHEST, it carries exponents (as `transposed` does)
        acum = dot(tri, da, (((1,), (0,)), ((), ())), precision=_HI)
        acum_t, dl_t = transposed(acum), transposed(dl)      # [hb, q]
        cb = dot(cf, bf, (((1,), (1,)), ((), ())))           # C_i . B_j
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1) // dh
        row_head = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) // dh
        # graft-lint: ok at most _BLOCK_TILES tiles a block (head_block), `pack` heads (<= 128 // d_head) a tile
        for p in range(hb // pack):
            cols = slice(p * w, (p + 1) * w)
            xt = _load_rows(x_ref, r0, q, cols)              # [q, w]
            s0 = jnp.where(first, jnp.where(fresh, 0.0, s_ref[cols, :]),
                           so_ref[cols, :])                  # [w, ds]
            y_in = jnp.zeros((q, w), _F32)
            e_ac = jnp.zeros((q, w), _F32)
            wmix = jnp.zeros((q, w), _F32)
            dtot = jnp.zeros((w, 1), _F32)
            # graft-lint: ok see the tiles' loop: pack <= 128 // d_head
            for j in range(pack):
                h = p * pack + j
                # graft-lint: ok a static one-lane slice of a value (here and below); compiled for the chip in tests/test_mosaic_compile.py
                ac = acum[:, h:h + 1]                        # [q, 1]
                scores = causal_scores(cb, ac, acum_t[h:h + 1, :],
                                       dl_t[h:h + 1, :])
                mine = lane_head == j
                y_in = jnp.where(mine, dot(scores, xt,
                                           (((1,), (0,)), ((), ()))), y_in)
                e_ac = jnp.where(mine, jnp.exp(ac), e_ac)
                # graft-lint: ok static one-lane slice, as above
                a_tot = acum[q - 1:q, h:h + 1]               # [1, 1]
                # graft-lint: ok static one-lane slice, as above
                d_col = dl[:, h:h + 1]
                wmix = jnp.where(
                    mine, jnp.exp(jnp.broadcast_to(a_tot, (q, 1)) - ac)
                    * d_col, wmix)
                dtot = jnp.where(row_head == j, jnp.exp(
                    jnp.broadcast_to(a_tot, (w, 1))), dtot)
            y_c = y_in + e_ac * dot(cf, s0, (((1,), (1,)), ((), ())))
            # the state after the unit's n rows (rows past n decay by 1 and
            # add nothing)
            so_ref[cols, :] = dtot * s0 + dot(wmix * xt, bf,
                                              (((0,), (0,)), ((), ())))
            _store_rows(y_ref, r0, n, cols, y_c)


# A jit of its own: the layers of a step call it with the same shapes, so a
# rung traces and lowers the kernel once and not once a layer (off-chip, at
# granite's widths: 0.25 + 0.26 s a rung against 0.17 + 0.16 s a layer).
@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "groups", "chunk"))
def ssm_slot_update(state, seg, work, n_work, x, b, c, delta, a_head, *,
                    heads: int, d_head: int, d_state: int, groups: int,
                    chunk: int):
    """``(y [t, heads x d_head] float32, state)`` after one step.

    ``state`` ``[slots, heads x d_head, d_state]`` float32 (donated by the
    caller's jit: updated in place); ``seg`` / ``work`` / ``n_work`` the
    step's plan; ``x [t, heads x d_head]``, ``b`` / ``c [t, groups x
    d_state]``, ``delta [t, heads]`` (after the softplus) the flat batch's
    rows; ``a_head [heads]`` the negative decay rates.  Rows no segment holds
    come back zero."""
    t = x.shape[0]
    slots = state.shape[0]
    rep = heads // groups
    q = min(chunk, t)
    hb = head_block(heads, d_head, d_state, rep)
    pack = math.gcd(hb, max(1, 128 // d_head))   # heads of one 128-column tile
    nblk, width = heads // hb, hb * d_head
    (slot, row0, n, flags), n_items, n_lanes, lane_row = plan_items(
        seg, work, n_work)

    rows_p = t + _window(q)
    pad = lambda v: jnp.pad(v.astype(_F32),                  # noqa: E731
                            ((0, rows_p - t), (0, 0)))
    x_p = pad(x)
    b_p = pad(b).reshape(rows_p, groups, d_state).transpose(1, 0, 2)
    c_p = pad(c).reshape(rows_p, groups, d_state).transpose(1, 0, 2)
    dl_p = pad(delta).reshape(rows_p, nblk, hb).transpose(1, 0, 2)
    a_blk = a_head.astype(_F32).reshape(nblk, 1, hb)

    # the lanes' rows, compacted, x transposed
    lanes = -(-slots // _LANE_COLS) * _LANE_COLS
    rows = jnp.clip(lane_row, 0, t - 1)
    d_lane = delta[rows].astype(_F32)                        # [slots, heads]
    a_lane = jnp.exp(d_lane * a_head.astype(_F32))
    xt = jnp.pad(x[rows].T, ((0, 0), (0, lanes - slots)))
    slots_p = -(-slots // 8) * 8
    lane_bc = lambda v: jnp.pad(                             # noqa: E731
        v[rows].astype(_F32).reshape(slots, groups, d_state),
        ((0, slots_p - slots), (0, 0), (0, 0))).transpose(1, 0, 2)

    group_of = lambda h: (h * hb) // rep                     # noqa: E731
    whole = lambda *shape: pl.BlockSpec(                     # noqa: E731
        (None,) + shape, lambda h, i, *_: (h, 0, 0))
    grouped = lambda *shape: pl.BlockSpec(                   # noqa: E731
        (None,) + shape, lambda h, i, *_: (group_of(h), 0, 0))
    state_spec = pl.BlockSpec((None, width, d_state),
                              lambda h, i, slot, *_: (slot[i], h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nblk, n_items),
        in_specs=[
            state_spec,
            pl.BlockSpec((rows_p, width), lambda h, i, *_: (0, h)),
            grouped(rows_p, d_state), grouped(rows_p, d_state),
            whole(rows_p, hb), whole(1, hb),
            pl.BlockSpec((width, lanes), lambda h, i, *_: (h, 0)),
            grouped(slots_p, d_state), grouped(slots_p, d_state),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            state_spec,
            pl.BlockSpec((rows_p, width), lambda h, i, *_: (0, h)),
            pl.BlockSpec((width, lanes), lambda h, i, *_: (h, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((width, d_state), _F32)],
    )
    new_state, y_rows, y_t = pl.pallas_call(
        functools.partial(_kernel, q=q, hb=hb, dh=d_head, pack=pack),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, _F32),
            jax.ShapeDtypeStruct((rows_p, heads * d_head), _F32),
            jax.ShapeDtypeStruct((heads * d_head, lanes), _F32),
        ],
        # operand 4 (after the four prefetched lists) is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        # graft-lint: ok the platform, constant in a process; read once a trace
        interpret=use_interpret(),
        name="ssm_slot_update",   # the device event's name
    )(slot, row0, n, flags, state, x_p, b_p, c_p, dl_p, a_blk, xt, lane_bc(b),
      lane_bc(c), a_lane, d_lane)
    y = y_rows[:t].at[
        jnp.where(jnp.arange(slots) < n_lanes, lane_row, t)].set(
            y_t.T[:slots], mode="drop")
    return y, new_state
