"""Interpret-mode parity suite for the pipelined work-unit prefill kernel.

ISSUE 3 tentpole proof, CPU-provable: the restructured
``ops/paged_prefill.py`` mainloop (double-buffered q/KV streaming,
plan-time block codes, unit pruning, tile packing) must match the
gather+flash oracle across the block-shape grid x {unmasked,
packed-mask, ragged} — including the packed-custom-mask variant whose
only on-chip run failed (the uint8-cast bug class at the in-kernel
bitmap expansion), so that path is exercised end-to-end off-chip.

Invariants pinned beyond oracle parity:

- **Packing is bit-exact.**  Rows outside a packed unit's span are
  identity steps of the online softmax (``p=0, alpha=1``), so packed
  and unpacked plans must produce BIT-IDENTICAL outputs.
- **Pruning is bit-exact.**  A pruned unit contributed nothing, so
  pruned and unpruned plans must also match bitwise.
- **CODE_FULL is bit-exact.**  ``where(all_true, s, -inf) == s``, so
  forcing every FULL unit back to PARTIAL must not change a single bit
  — the fast path is a pure specialization, never a numeric variant.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flashinfer_tpu.ops.paged_prefill import (
    CODE_FULL,
    CODE_PARTIAL,
    build_prefill_work_units,
    fused_paged_prefill,
)

HQ, HKV, D, PS = 4, 2, 32, 8

# the swept block-shape grid: (block_q, pages_per_chunk) — small enough
# for interpret mode, shaped to cover partial tiles, multi-chunk kv, and
# the single-chunk degenerate
BLOCK_GRID = [(32, 2), (64, 4), (128, 2)]

# ragged geometries: uniform chunked, mixed ragged with a zero-kv and a
# zero-qo request, and single long request (the causal-pruning shape)
GEOMETRIES = {
    "uniform": ([64, 64, 64], [128, 128, 128]),
    "ragged": ([40, 7, 130, 0, 65], [64, 24, 200, 16, 0]),
    "single_long": ([192], [256]),
}


def _setup(qo_lens, kv_lens, seed=0):
    rng = np.random.default_rng(seed)
    qo_indptr = np.concatenate([[0], np.cumsum(qo_lens)]).astype(np.int32)
    pages_per = [int(np.ceil(l / PS)) for l in kv_lens]
    kv_page_indptr = np.concatenate([[0], np.cumsum(pages_per)]).astype(
        np.int32)
    npages = max(int(kv_page_indptr[-1]), 1)
    kv_page_indices = rng.permutation(npages).astype(np.int32)
    total_q = int(qo_indptr[-1])
    q = jax.random.normal(jax.random.PRNGKey(seed), (total_q, HQ, D),
                          jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (npages, HKV, PS, D), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(seed + 2),
                           (npages, HKV, PS, D), jnp.float32)
    return qo_indptr, kv_page_indptr, kv_page_indices, q, kc, vc


def _run(qo_indptr, kv_page_indptr, kv_page_indices, kv_lens, q, kc, vc,
         bq, ppc, *, causal=True, window_left=-1, mask_flat=None,
         mask_total_bits=None, pack_tiles=True, prune=True,
         force_partial=False, return_lse=False):
    plan_np = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(kv_lens, np.int64), block_q=bq, pages_per_chunk=ppc,
        page_size=PS, mask_flat=mask_flat, mask_total_bits=mask_total_bits,
        causal=causal, window_left=window_left, pack_tiles=pack_tiles,
        prune=prune,
    )
    statics = dict(num_units=plan_np.pop("num_units"),
                   block_q=plan_np.pop("block_q"),
                   pages_per_chunk=plan_np.pop("pages_per_chunk"))
    stats = plan_np.pop("stats")
    if force_partial:
        plan_np["code"] = np.where(
            plan_np["code"] == CODE_FULL, CODE_PARTIAL, plan_np["code"]
        ).astype(np.int32)
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    out = fused_paged_prefill(
        q, kc, vc, plan, sm_scale=D ** -0.5, causal=causal,
        window_left=window_left, return_lse=return_lse, **statics,
    )
    if return_lse:      # (o, lse)
        return tuple(np.asarray(x) for x in out), stats, plan_np
    return np.asarray(out, np.float32), stats, plan_np


def _oracle(qo_indptr, kv_page_indptr, kv_page_indices, kv_lens, q, kc, vc,
            *, causal=True, window_left=-1, mask_flat=None):
    """Dense per-request attention with bottom-right (append) alignment —
    the gather+flash semantics the wrapper's fallback path implements."""
    qo_lens = qo_indptr[1:] - qo_indptr[:-1]
    total_q = int(qo_indptr[-1])
    ref = np.zeros((total_q, HQ, D), np.float32)
    off = 0
    for r in range(len(qo_lens)):
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        n_bits = int(qo_lens[r]) * int(kv_lens[r])
        m = (np.asarray(mask_flat[off:off + n_bits]).reshape(
            int(qo_lens[r]), int(kv_lens[r])) if mask_flat is not None
            and n_bits else None)
        off += n_bits
        if qe <= qs or kv_lens[r] == 0:
            continue
        pages = kv_page_indices[kv_page_indptr[r]:kv_page_indptr[r + 1]]
        kr = np.asarray(kc)[pages].transpose(0, 2, 1, 3).reshape(
            -1, HKV, D)[: kv_lens[r]]
        vr = np.asarray(vc)[pages].transpose(0, 2, 1, 3).reshape(
            -1, HKV, D)[: kv_lens[r]]
        qr = np.asarray(q)[qs:qe]
        qpos = kv_lens[r] - qo_lens[r] + np.arange(qo_lens[r])
        kpos = np.arange(kv_lens[r])
        kg = np.repeat(kr, HQ // HKV, axis=1)
        vg = np.repeat(vr, HQ // HKV, axis=1)
        s = np.einsum("qhd,khd->hqk", qr, kg) * (D ** -0.5)
        valid = np.ones((qo_lens[r], kv_lens[r]), bool)
        if m is not None:
            valid &= m
        elif causal:
            valid &= kpos[None, :] <= qpos[:, None]
        if window_left >= 0:
            valid &= kpos[None, :] >= qpos[:, None] - window_left
        s = np.where(valid[None], s, -np.inf)
        mx = s.max(-1, keepdims=True)
        p = np.where(valid[None], np.exp(s - np.where(
            np.isfinite(mx), mx, 0.0)), 0.0)
        l = p.sum(-1, keepdims=True)
        ref[qs:qe] = np.einsum(
            "hqk,khd->qhd", np.where(l > 0, p / np.where(l > 0, l, 1.0), 0),
            vg)
    return ref


@pytest.mark.quick
@pytest.mark.parametrize("bq,ppc", BLOCK_GRID)
@pytest.mark.parametrize("geom", sorted(GEOMETRIES))
def test_unmasked_parity_and_packing_bitwise(bq, ppc, geom):
    """Unmasked causal cell of the suite: oracle parity at every swept
    block shape, plus the packing/pruning bitwise invariants."""
    qo_lens, kv_lens = GEOMETRIES[geom]
    args = _setup(qo_lens, kv_lens)
    out, stats, _ = _run(*args[:3], kv_lens, *args[3:], bq, ppc)
    ref = _oracle(args[0], args[1], args[2], kv_lens, *args[3:])
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # packing and pruning are identity transforms, bit for bit
    out_unpacked, stats_u, _ = _run(
        *args[:3], kv_lens, *args[3:], bq, ppc, pack_tiles=False)
    np.testing.assert_array_equal(out, out_unpacked)
    out_unpruned, _, _ = _run(
        *args[:3], kv_lens, *args[3:], bq, ppc, prune=False)
    np.testing.assert_array_equal(out, out_unpruned)
    # the single long causal request must actually prune above-diagonal
    # chunks and pack at least as tight as the unpacked plan
    if geom == "single_long":
        assert stats["units_pruned"] > 0
    assert stats["units"] <= stats_u["units"]


@pytest.mark.parametrize("bq,ppc", BLOCK_GRID)
def test_full_code_fast_path_is_bitwise_pure(bq, ppc):
    """CODE_FULL is a specialization, not an approximation: demoting
    every FULL unit to PARTIAL must reproduce the output bit for bit."""
    qo_lens, kv_lens = GEOMETRIES["uniform"]
    args = _setup(qo_lens, kv_lens, seed=5)
    out, _, plan_np = _run(*args[:3], kv_lens, *args[3:], bq, ppc)
    if bq <= min(qo_lens):
        # tiles fit inside requests -> interior below-diagonal units must
        # classify FULL (bq > qo_len can never fill a tile's rows)
        assert (plan_np["code"] == CODE_FULL).any(), (
            f"uniform chunked geometry should classify interior units "
            f"FULL (codes={plan_np['code']})")
    out_partial, _, _ = _run(*args[:3], kv_lens, *args[3:], bq, ppc,
                             force_partial=True)
    np.testing.assert_array_equal(out, out_partial)


@pytest.mark.parametrize("bq,ppc", BLOCK_GRID)
@pytest.mark.parametrize("geom", ["uniform", "ragged"])
@pytest.mark.parametrize("use_native", [True, False])
def test_packed_mask_parity(bq, ppc, geom, use_native):
    """Packed-custom-mask cell: the EXACT in-kernel path that failed on
    chip (uint8 bitmap -> int32 widen -> f32 selector-dot expansion,
    ops/paged_prefill.py mask_bits) runs in interpret mode against the
    dense masked oracle, from LSB-first packed bytes end-to-end, with
    the C++ and numpy mask planners both covered."""
    from flashinfer_tpu import native

    if use_native and native.get_lib() is None:
        pytest.skip("native planner unavailable")
    qo_lens, kv_lens = GEOMETRIES[geom]
    args = _setup(qo_lens, kv_lens, seed=7)
    rng = np.random.default_rng(11)
    total_bits = int(np.sum(np.asarray(qo_lens) * np.asarray(kv_lens)))
    mask_bool = rng.random(total_bits) < 0.5
    packed_bytes = np.packbits(mask_bool, bitorder="little")

    lib_save = native._LIB
    if not use_native:
        native._LIB = None
    try:
        out, _, plan_np = _run(
            *args[:3], kv_lens, *args[3:], bq, ppc, causal=False,
            mask_flat=packed_bytes, mask_total_bits=total_bits)
    finally:
        native._LIB = lib_save
    ref = _oracle(args[0], args[1], args[2], kv_lens, *args[3:],
                  causal=False, mask_flat=mask_bool)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # the kernel consumed a genuine uint8 bitmap (the failing dtype)
    assert plan_np["mask_bytes"].dtype == np.uint8


def test_masked_kernel_never_casts_uint8_to_float_directly():
    """Regression pin for the on-chip failure class itself: Mosaic has
    no uint8->float cast ('Unsupported cast', banked 2026-07-31), so the
    bitmap expansion must widen through int32 first.  The parity tests
    above prove the path's NUMERICS off-chip; this pins the lowering
    shape so the compile-time failure cannot silently return."""
    import ast
    import inspect

    from flashinfer_tpu.ops import paged_prefill

    src = inspect.getsource(paged_prefill)
    tree = ast.parse(src)
    hits = []
    for node in ast.walk(tree):
        # any <expr>.astype(jnp.float32) where <expr> mentions mask_ref
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and "mask_ref" in ast.dump(node.func.value)):
            hits.append(ast.unparse(node))
    assert hits, "mask bitmap expansion disappeared — update this pin"
    for call in hits:
        assert "int32" in call and "float32" not in call.split(")")[0], (
            f"mask bytes must widen uint8 -> int32 before any float "
            f"cast (Mosaic 'Unsupported cast' wedge class): {call}")


@pytest.mark.parametrize("bq,ppc", [(64, 2)])
def test_window_left_parity_and_window_pruning(bq, ppc):
    qo_lens, kv_lens = GEOMETRIES["single_long"]
    args = _setup(qo_lens, kv_lens, seed=9)
    out, stats, _ = _run(*args[:3], kv_lens, *args[3:], bq, ppc,
                         window_left=48)
    ref = _oracle(args[0], args[1], args[2], kv_lens, *args[3:],
                  window_left=48)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # chunks entirely below the window are plan-pruned: strictly more
    # pruning than the causal-only plan
    _, stats_causal, _ = _run(*args[:3], kv_lens, *args[3:], bq, ppc)
    assert stats["units_pruned"] > stats_causal["units_pruned"]


def test_wrapper_fused_backend_masked_matches_gather_path():
    """Wrapper-level end-to-end: BatchPrefillWithPagedKVCacheWrapper on
    the explicit fused backend with a packed custom mask vs the gather
    (xla) fallback — the masked-prefill surface PARITY.md restated to
    'fix committed, on-chip re-proof pending', provable here off-chip."""
    import flashinfer_tpu as fi

    qo_lens, kv_lens = [24, 40], [48, 64]
    (qo_indptr, kv_page_indptr, kv_page_indices, q, kc, vc) = _setup(
        qo_lens, kv_lens, seed=13)
    q = q.astype(jnp.bfloat16)
    kc = kc.astype(jnp.bfloat16)
    vc = vc.astype(jnp.bfloat16)
    # HND cache layout for the fused path
    kc_hnd, vc_hnd = kc, vc
    last_page = (np.asarray(kv_lens)
                 - (np.asarray([np.ceil(l / PS) for l in kv_lens],
                               np.int32) - 1) * PS).astype(np.int32)
    rng = np.random.default_rng(17)
    total_bits = int(np.sum(np.asarray(qo_lens) * np.asarray(kv_lens)))
    packed_mask = np.packbits(rng.random(total_bits) < 0.6,
                              bitorder="little")

    outs = {}
    for backend in ("pallas_fused", "xla"):
        w = fi.BatchPrefillWithPagedKVCacheWrapper(
            kv_layout="HND", backend=backend)
        w.plan(
            qo_indptr, kv_page_indptr, kv_page_indices, last_page,
            HQ, HKV, D, PS, causal=True, packed_custom_mask=packed_mask,
        )
        if backend == "pallas_fused":
            cfg = w.fused_prefill_config
            assert cfg is not None and cfg["block_q"] > 0
        outs[backend] = np.asarray(
            w.run(q, (kc_hnd, vc_hnd)), np.float32)
    np.testing.assert_allclose(outs["pallas_fused"], outs["xla"],
                               rtol=3e-2, atol=3e-2)


def test_wrapper_live_retune_refreshes_plan_and_stats(monkeypatch):
    """In-run autotune swap: when `choose_one` picks a different block
    config than the planned one, the wrapper must rebuild the fused
    plan AND refresh `fused_prefill_stats` — the plan stays the
    (unit_plan, statics) 2-tuple every consumer unpacks, and the stats
    describe the NEW launch shape (the roofline cost model attributes
    from them; stale stats would attribute the old grid)."""
    import flashinfer_tpu as fi
    from flashinfer_tpu import autotuner
    from flashinfer_tpu.ops.paged_prefill import block_candidates

    qo_lens, kv_lens = [24, 40], [48, 64]
    (qo_indptr, kv_page_indptr, kv_page_indices, q, kc, vc) = _setup(
        qo_lens, kv_lens, seed=3)
    q = q.astype(jnp.bfloat16)
    kc = kc.astype(jnp.bfloat16)
    vc = vc.astype(jnp.bfloat16)
    last_page = (np.asarray(kv_lens)
                 - (np.asarray([np.ceil(l / PS) for l in kv_lens],
                               np.int32) - 1) * PS).astype(np.int32)
    w = fi.BatchPrefillWithPagedKVCacheWrapper(
        kv_layout="HND", backend="pallas_fused")
    w.plan(qo_indptr, kv_page_indptr, kv_page_indices, last_page,
           HQ, HKV, D, PS, causal=True)
    cfg0 = w.fused_prefill_config
    stats0 = w.fused_prefill_stats
    assert cfg0 is not None and stats0 is not None

    other = next(
        c for c in block_candidates(PS)
        if (int(c[0]), int(c[1]))
        != (cfg0["block_q"], cfg0["pages_per_chunk"]))
    monkeypatch.setattr(
        autotuner.AutoTuner, "choose_one",
        lambda self, op, key, cands, runner, default=None, module=None:
        other)
    with autotuner.autotune():
        out = np.asarray(w.run(q, (kc, vc)), np.float32)

    cfg1 = w.fused_prefill_config
    assert (cfg1["block_q"], cfg1["pages_per_chunk"]) \
        == (int(other[0]), int(other[1]))
    stats1 = w.fused_prefill_stats
    assert stats1 != stats0  # per-config unit/tile/cell counts moved
    assert stats1["mxu_cells_valid"] == stats0["mxu_cells_valid"]
    # the swapped plan is still runnable and numerically right (vs the
    # gather fallback)
    ref = fi.BatchPrefillWithPagedKVCacheWrapper(
        kv_layout="HND", backend="xla")
    ref.plan(qo_indptr, kv_page_indptr, kv_page_indices, last_page,
             HQ, HKV, D, PS, causal=True)
    np.testing.assert_allclose(out, np.asarray(ref.run(q, (kc, vc)),
                                               np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# One write-back unit a packed tile (ISSUE 40)
# ---------------------------------------------------------------------------

# (qo_lens, kv_lens, causal, window_left) at block_q 64, 2 pages a chunk:
EMPTY_SPAN_PLANS = {
    # 48 decode rows that attend nothing at this level, on one tile
    "one_row_lanes": ([1] * 48, [0] * 48, True, -1),
    # real units with empty spans before, between and after them, over two
    # tiles (the 40-row request crosses the boundary)
    "real_between_empty": ([1, 5, 1, 30, 2, 40, 1, 9],
                           [0, 24, 0, 10, 0, 40, 0, 0], True, -1),
    # a rung with no KV at all: two tiles, five spans
    "all_empty_rung": ([40, 30, 50, 8], [0, 0, 0, 0], True, -1),
    # a windowed plan: the 70-row request's rows in tile 0 lie before its
    # first position (all pruned there), its rows in tile 1 attend
    "windowed": ([3, 70, 5, 2], [10, 8, 0, 90], True, 7),
    # the same without the causal rule (level 0's form, less the mask)
    "non_causal": ([3, 1, 1, 70, 5], [10, 0, 0, 33, 0], False, -1),
}


def _plan_and_run(args, kv_lens, causal, window_left, pack_tiles=True):
    """(plan arrays with their ``stats``, o, lse) at block_q 64, 2 pages a
    chunk."""
    (o, lse), stats, plan = _run(
        *args[:3], kv_lens, *args[3:], 64, 2, causal=causal,
        window_left=window_left, pack_tiles=pack_tiles, return_lse=True)
    return dict(plan, stats=stats), o, lse


@pytest.mark.parametrize("name", sorted(EMPTY_SPAN_PLANS))
def test_a_packed_tile_keeps_one_write_back_unit(name, monkeypatch):
    """A span with nothing to attend gets a fallback unit only where its
    packed tile would hold no unit.  Against the parent's plan (every such
    span its own fallback unit: the planner with the cut taken out): the
    kept units are the parent's in the parent's order, at most one fallback
    unit a tile and none in a tile with a real unit, and the launch's ``o``
    and ``lse`` are the parent's bit for bit (rows no unit covers leave the
    tile's reset state).  An unpacked plan is the parent's array for
    array."""
    import flashinfer_tpu.ops.paged_prefill as pp

    qo_lens, kv_lens, causal, wl = EMPTY_SPAN_PLANS[name]
    args = _setup(qo_lens, kv_lens, seed=4)
    plan, o, lse = _plan_and_run(args, kv_lens, causal, wl)
    unpacked, o_u, lse_u = _plan_and_run(args, kv_lens, causal, wl,
                                         pack_tiles=False)
    monkeypatch.setattr(pp, "_one_fallback_a_tile", lambda u, w: (u, w))
    parent, o_p, lse_p = _plan_and_run(args, kv_lens, causal, wl)
    parent_u, _, _ = _plan_and_run(args, kv_lens, causal, wl,
                                   pack_tiles=False)

    np.testing.assert_array_equal(o, o_p)
    np.testing.assert_array_equal(lse, lse_p)
    np.testing.assert_array_equal(o, o_u)     # packing stays bit-exact
    np.testing.assert_array_equal(lse, lse_u)
    ref = _oracle(args[0], args[1], args[2], kv_lens, *args[3:],
                  causal=causal, window_left=wl)
    np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)

    assert plan["stats"]["packed"] and not unpacked["stats"]["packed"]
    keys = ("qstart", "rowlo", "rowhi", "qpos0", "kvstart", "kvlen", "code")
    n, n_p = plan["stats"]["units"], parent["stats"]["units"]
    assert n < n_p and plan["n_units"] == n
    units = [tuple(int(plan[k][i]) for k in keys) for i in range(n)]
    units_p = [tuple(int(parent[k][i]) for k in keys) for i in range(n_p)]
    fallback = [u for u in units if u[5] == 0]
    tiles_real = {u[0] for u in units if u[5] > 0}
    assert [u for u in units_p if u[5] > 0] == [u for u in units if u[5] > 0]
    assert len({u[0] for u in fallback}) == len(fallback)
    assert not {u[0] for u in fallback} & tiles_real
    assert {u[0] for u in units} == {u[0] for u in units_p}   # every tile
    # ... in the parent's order, first and last of a tile flagged
    it = iter(units_p)
    assert all(u in it for u in units)
    for t in {u[0] for u in units}:
        idx = [i for i, u in enumerate(units) if u[0] == t]
        assert idx == list(range(idx[0], idx[-1] + 1))
        assert [int(plan["first"][i]) for i in idx] == \
            [1] + [0] * (len(idx) - 1)
        assert [int(plan["wout"][i]) for i in idx] == \
            [0] * (len(idx) - 1) + [1]
    assert plan["stats"]["units_fallback"] == len(fallback)
    assert parent["stats"]["units_fallback"] == n_p - n + len(fallback)
    if name in ("one_row_lanes", "all_empty_rung"):
        assert n == len(fallback) == plan["stats"]["tiles"]
        assert not o.any() and (lse < -1e29).all()

    for k, v in unpacked.items():
        if isinstance(v, np.ndarray):
            np.testing.assert_array_equal(v, parent_u[k], err_msg=k)
    assert unpacked["stats"] == parent_u["stats"]


def test_an_aligned_plan_keeps_a_fallback_unit_a_request():
    """Requests that each start on a tile boundary are unpacked whatever
    ``pack_tiles`` says: every span is its own tile, and an empty one keeps
    its fallback unit."""
    args = _setup([64, 64, 64], [24, 0, 0], seed=2)
    plan, o, lse = _plan_and_run(args, [24, 0, 0], True, -1)
    assert not plan["stats"]["packed"]
    assert plan["stats"]["units"] == 2 + 2
    assert plan["stats"]["units_fallback"] == 2
    assert not o[64:].any() and (lse[64:] < -1e29).all()
