"""Fused prefill INGEST parity suite (ISSUE 14 tentpole proof).

The fused launch — RoPE + KV-quantize-append + attention folded into
the work-unit prefill mainloop (``ops/paged_prefill.
fused_paged_prefill_ingest``) — is pinned against the separate-op
ORACLE composition: ``rotate_at_positions_static`` -> the matching
``append_paged_kv_cache[_quant_{int8,fp8}]`` -> the proven work-unit
attention kernel.  The bar (ISSUE 14 acceptance):

- **f32 is bitwise.**  Same rotation math (constant-base freq pow),
  same online-softmax walk — output AND cache bits identical.
- **Quantized caches are bit-for-bit.**  The in-kernel quantize is the
  quant-append formula verbatim, so int8/fp8 cache bits equal the
  composed append's on every valid row (rows past a sequence's end in
  its last partial page are deterministically zeroed by the fused
  write-back — excluded by contract, see the kernel docstring).
- Causal / windowed / packed-custom-mask rungs all hold, write-only
  units (chunks attention pruned everywhere) still reach the cache,
  and the append-only form serves the ``rope_quantize_fp8_append_
  paged_kv_cache`` reroute with the composed tier as its oracle.
- The engine kernel tier keeps its token pins and its
  one-trace-per-rung budget.
- The analysis registrations (L007 planner pair, L009 knob launch,
  L006 tuning sections) cannot skew from the real modules.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flashinfer_tpu.ops.paged_prefill import (
    CODE_WRITE_ONLY,
    build_prefill_ingest_units,
    build_prefill_work_units,
    fused_paged_prefill,
    fused_paged_prefill_ingest,
)
from flashinfer_tpu.page import (
    append_paged_kv_cache,
    append_paged_kv_cache_quant_fp8,
    append_paged_kv_cache_quant_int8,
)
from flashinfer_tpu.rope import rotate_at_positions_static

HQ, HKV, D, PS = 4, 2, 32, 8
BQ, PPC = 32, 2

# from-scratch ingest geometries: qo_lens == kv_lens (the raw rows ARE
# the planned KV axis); mixed ragged includes a zero-length request
GEOMETRIES = {
    "uniform": [64, 64, 64],
    "ragged": [40, 7, 130, 0, 65],
    "single_long": [192],
}


def _setup(lens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    qo_indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    pages_per = [int(np.ceil(n / PS)) for n in lens]
    kv_page_indptr = np.concatenate([[0], np.cumsum(pages_per)]).astype(
        np.int64)
    npages = max(int(kv_page_indptr[-1]), 1)
    kv_page_indices = rng.permutation(npages).astype(np.int64)
    total = int(qo_indptr[-1])
    q = jax.random.normal(jax.random.PRNGKey(seed), (total, HQ, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (total, HKV, D),
                          dtype)
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (total, HKV, D),
                          dtype)
    return qo_indptr, kv_page_indptr, kv_page_indices, q, k, v


def _positions(lens):
    kv_pos = np.concatenate(
        [np.arange(n) for n in lens] or [np.zeros(0)]).astype(np.int32)
    kv_req = np.repeat(np.arange(len(lens)), lens).astype(np.int32)
    return kv_pos, kv_req


def _fused(qo_indptr, kv_page_indptr, kv_page_indices, lens, q, k, v,
           kc, vc, *, causal=True, window_left=-1, mask_flat=None,
           mask_total_bits=None, kv_quant="none", ks=1.0, vs=1.0,
           attend=True, fused_ingest=True):
    plan_np = build_prefill_ingest_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(lens, np.int64), block_q=BQ, pages_per_chunk=PPC,
        page_size=PS, mask_flat=mask_flat,
        mask_total_bits=mask_total_bits, causal=causal,
        window_left=window_left, fused_ingest=fused_ingest,
    )
    statics = dict(num_units=plan_np.pop("num_units"),
                   block_q=plan_np.pop("block_q"),
                   pages_per_chunk=plan_np.pop("pages_per_chunk"))
    stats = plan_np.pop("stats")
    plan = {kk: jnp.asarray(vv) for kk, vv in plan_np.items()}
    total = int(qo_indptr[-1])
    if attend:
        tq_pad = max(BQ, -(-total // BQ) * BQ)
        qp = jnp.pad(q, ((0, tq_pad - total), (0, 0), (0, 0)))
    else:
        qp = None
    out = fused_paged_prefill_ingest(
        qp, k, v, kc, vc, plan, sm_scale=D ** -0.5, causal=causal,
        window_left=window_left, attend=attend, kv_quant=kv_quant,
        k_scale=ks, v_scale=vs, **statics,
    )
    if not attend:
        return out, stats
    o, caches = out
    return o[:total], caches, stats


def _composed(qo_indptr, kv_page_indptr, kv_page_indices, lens, q, k, v,
              kc, vc, *, causal=True, window_left=-1, mask_flat=None,
              mask_total_bits=None, kv_quant="none", ks=1.0, vs=1.0):
    """The separate-op oracle: static-rotate -> matching append ->
    work-unit attention over the post-append cache, scales folded the
    decode-kernel way (k into sm, v on the output)."""
    kv_pos, kv_req = _positions(lens)
    q_rot = rotate_at_positions_static(q, jnp.asarray(
        np.concatenate([np.arange(n) for n in lens] or [np.zeros(0)])
        .astype(np.int32)))
    k_rot = rotate_at_positions_static(k, jnp.asarray(kv_pos))
    kvi = jnp.asarray(kv_page_indices)
    kvp = jnp.asarray(kv_page_indptr)
    if kv_quant == "int8":
        caches = append_paged_kv_cache_quant_int8(
            k_rot, v, jnp.asarray(kv_req), jnp.asarray(kv_pos), (kc, vc),
            kvi, kvp, jnp.float32(ks), jnp.float32(vs), "HND")
    elif kv_quant == "fp8":
        caches = append_paged_kv_cache_quant_fp8(
            k_rot, v, jnp.asarray(kv_req), jnp.asarray(kv_pos), (kc, vc),
            kvi, kvp, jnp.float32(ks), jnp.float32(vs), "HND")
    else:
        caches = append_paged_kv_cache(
            k_rot, v, jnp.asarray(kv_req), jnp.asarray(kv_pos), (kc, vc),
            kvi, kvp, None, "HND")
    plan_np = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(lens, np.int64), block_q=BQ, pages_per_chunk=PPC,
        page_size=PS, mask_flat=mask_flat,
        mask_total_bits=mask_total_bits, causal=causal,
        window_left=window_left,
    )
    statics = dict(num_units=plan_np.pop("num_units"),
                   block_q=plan_np.pop("block_q"),
                   pages_per_chunk=plan_np.pop("pages_per_chunk"))
    plan_np.pop("stats")
    plan = {kk: jnp.asarray(vv) for kk, vv in plan_np.items()}
    total = int(qo_indptr[-1])
    tq_pad = max(BQ, -(-total // BQ) * BQ)
    qp = jnp.pad(q_rot, ((0, tq_pad - total), (0, 0), (0, 0)))
    sm = D ** -0.5 * (ks if kv_quant != "none" else 1.0)
    out = fused_paged_prefill(
        qp, caches[0], caches[1], plan, sm_scale=sm, causal=causal,
        window_left=window_left, **statics)[:total]
    if kv_quant != "none":
        out = (out.astype(jnp.float32) * vs).astype(q.dtype)
    return out, caches


def _valid_cache_rows(kv_page_indptr, kv_page_indices, lens, cache):
    """Flat [sum(lens), HKV, D] view of the cache's VALID rows only
    (rows past each sequence's end are outside the parity contract)."""
    rows = []
    arr = np.asarray(cache)
    for r, n in enumerate(lens):
        pages = kv_page_indices[kv_page_indptr[r]:kv_page_indptr[r + 1]]
        for j, p in enumerate(pages):
            nn = min(PS, n - j * PS)
            rows.append(arr[p].transpose(1, 0, 2)[:nn])
    return np.concatenate(rows) if rows else np.zeros((0, HKV, D))


# ---------------------------------------------------------------------------
# kernel-level fused-vs-composed parity
# ---------------------------------------------------------------------------


@pytest.mark.quick
@pytest.mark.parametrize("geom", sorted(GEOMETRIES))
def test_fused_vs_composed_f32_bitwise(geom):
    """f32: output and cache bits of the fused launch == the separate
    rotate -> append -> attend composition, bitwise."""
    lens = GEOMETRIES[geom]
    qo, kvp, kvi, q, k, v = _setup(lens, seed=1)
    npages = max(int(kvp[-1]), 1)
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float32)
    o_f, (kc_f, vc_f), stats = _fused(qo, kvp, kvi, lens, q, k, v,
                                      z(), z())
    o_c, (kc_c, vc_c) = _composed(qo, kvp, kvi, lens, q, k, v, z(), z())
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, kc_f),
        _valid_cache_rows(kvp, kvi, lens, kc_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, vc_f),
        _valid_cache_rows(kvp, kvi, lens, vc_c))
    assert stats["ingest_chunks"] > 0


@pytest.mark.parametrize("window_left", [0, 17, 40])
def test_fused_vs_composed_windowed(window_left):
    lens = [40, 7, 130, 0, 65]
    qo, kvp, kvi, q, k, v = _setup(lens, seed=2)
    npages = max(int(kvp[-1]), 1)
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float32)
    o_f, (kc_f, vc_f), stats = _fused(
        qo, kvp, kvi, lens, q, k, v, z(), z(), window_left=window_left)
    o_c, (kc_c, vc_c) = _composed(
        qo, kvp, kvi, lens, q, k, v, z(), z(), window_left=window_left)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, kc_f),
        _valid_cache_rows(kvp, kvi, lens, kc_c))


def test_write_only_units_complete_the_cache():
    """A custom mask whose first KV chunk no q row attends prunes that
    chunk from EVERY tile — it must still reach the cache via
    CODE_WRITE_ONLY units (empty row span, no MXU work)."""
    lens = [48]  # 3 chunks of 16; chunk 0's columns all-masked
    mask = np.zeros((48, 48), bool)
    for i in range(48):
        mask[i, 16 + (i % 32)] = True  # every row attends, cols <16 never
    mask_flat = mask.reshape(-1)
    qo, kvp, kvi, q, k, v = _setup(lens, seed=3)
    npages = int(kvp[-1])
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float32)
    plan_np = build_prefill_ingest_units(
        qo, kvp, kvi, np.asarray(lens, np.int64), block_q=BQ,
        pages_per_chunk=PPC, page_size=PS, causal=False,
        mask_flat=mask_flat)
    assert plan_np["stats"]["ingest_write_only_units"] > 0
    assert np.any(plan_np["code"] == CODE_WRITE_ONLY)
    o_f, (kc_f, vc_f), _ = _fused(qo, kvp, kvi, lens, q, k, v, z(), z(),
                                  causal=False, mask_flat=mask_flat)
    o_c, (kc_c, vc_c) = _composed(qo, kvp, kvi, lens, q, k, v, z(), z(),
                                  causal=False, mask_flat=mask_flat)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, kc_f),
        _valid_cache_rows(kvp, kvi, lens, kc_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, vc_f),
        _valid_cache_rows(kvp, kvi, lens, vc_c))


def test_fused_vs_composed_packed_mask():
    """The packed-custom-mask rung: a random per-request bitmap (the
    MaskMode::CUSTOM form) through the in-kernel bitmap expansion."""
    lens = [40, 33]
    rng = np.random.default_rng(7)
    # keep the diagonal set so no q row attends the empty set
    mask_flat = np.concatenate(
        [((rng.random((n, n)) < 0.6) | np.eye(n, dtype=bool)).reshape(-1)
         for n in lens])
    qo, kvp, kvi, q, k, v = _setup(lens, seed=4)
    npages = int(kvp[-1])
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float32)
    o_f, (kc_f, _vf), _ = _fused(
        qo, kvp, kvi, lens, q, k, v, z(), z(), causal=False,
        mask_flat=mask_flat)
    o_c, (kc_c, _vc) = _composed(
        qo, kvp, kvi, lens, q, k, v, z(), z(), causal=False,
        mask_flat=mask_flat)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_c))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, kc_f),
        _valid_cache_rows(kvp, kvi, lens, kc_c))


@pytest.mark.parametrize("kv_quant,cache_dtype", [
    ("int8", jnp.int8), ("fp8", jnp.float8_e4m3fn)])
def test_quantized_cache_bits_and_output(kv_quant, cache_dtype):
    """int8/fp8: cache bits == ``append_paged_kv_cache_quant_*``
    bit-for-bit on every valid row; attention output == the composed
    attend-the-codes path bitwise (same codes, same kernel walk)."""
    lens = [40, 7, 130, 0, 65]
    qo, kvp, kvi, q, k, v = _setup(lens, seed=5)
    npages = int(kvp[-1])
    ks, vs = 0.05, 0.04
    z = lambda: jnp.zeros((npages, HKV, PS, D), cache_dtype)
    o_f, (kc_f, vc_f), _ = _fused(
        qo, kvp, kvi, lens, q, k, v, z(), z(), kv_quant=kv_quant,
        ks=ks, vs=vs)
    o_c, (kc_c, vc_c) = _composed(
        qo, kvp, kvi, lens, q, k, v, z(), z(), kv_quant=kv_quant,
        ks=ks, vs=vs)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_c))
    for f, c in ((kc_f, kc_c), (vc_f, vc_c)):
        np.testing.assert_array_equal(
            _valid_cache_rows(kvp, kvi, lens, f).view(np.uint8),
            _valid_cache_rows(kvp, kvi, lens, c).view(np.uint8))


def test_append_only_form_and_pos_offsets():
    """``attend=False`` (the reroute's form) with per-request position
    offsets: cache bits == the composed rotate-at-global-positions ->
    quant-append, bit-for-bit."""
    lens = [24, 9, 16]
    pos0 = [0, 8, 16]  # page-aligned global starts
    qo, kvp, kvi, _q, k, v = _setup(lens, seed=6)
    npages = int(kvp[-1])
    scale = 0.5
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float8_e4m3fn)
    plan_np = build_prefill_ingest_units(
        qo, kvp, kvi, np.asarray(lens, np.int64), block_q=8,
        pages_per_chunk=PPC, page_size=PS, causal=False, prune=False,
        fused_ingest={"pos_offsets": np.asarray(pos0, np.int64)})
    statics = dict(num_units=plan_np.pop("num_units"),
                   block_q=plan_np.pop("block_q"),
                   pages_per_chunk=plan_np.pop("pages_per_chunk"))
    plan_np.pop("stats")
    plan = {kk: jnp.asarray(vv) for kk, vv in plan_np.items()}
    kc_f, vc_f = fused_paged_prefill_ingest(
        None, k, v, z(), z(), plan, causal=False, attend=False,
        kv_quant="fp8", k_scale=scale, v_scale=scale, **statics)
    # composed: rotate at the GLOBAL positions, append at the local
    kv_pos, kv_req = _positions(lens)
    gpos = kv_pos + np.repeat(np.asarray(pos0), lens).astype(np.int32)
    k_rot = rotate_at_positions_static(k, jnp.asarray(gpos))
    kc_c, vc_c = append_paged_kv_cache_quant_fp8(
        k_rot, v, jnp.asarray(kv_req), jnp.asarray(kv_pos), (z(), z()),
        jnp.asarray(kvi), jnp.asarray(kvp), jnp.float32(scale),
        jnp.float32(scale), "HND")
    for f, c in ((kc_f, kc_c), (vc_f, vc_c)):
        np.testing.assert_array_equal(
            _valid_cache_rows(kvp, kvi, lens, f).view(np.uint8),
            _valid_cache_rows(kvp, kvi, lens, c).view(np.uint8))


# ---------------------------------------------------------------------------
# wrapper run_ingest
# ---------------------------------------------------------------------------


def _wrapper_setup(lens, monkeypatch, dtype=jnp.float32):
    monkeypatch.setenv("FLASHINFER_TPU_BACKEND", "pallas")
    import flashinfer_tpu as fi

    qo, kvp, kvi, q, k, v = _setup(lens, seed=8, dtype=dtype)
    last = np.asarray([n % PS or PS for n in lens], np.int32)
    w = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="HND")
    return w, qo, kvp, kvi, last, q, k, v


@pytest.mark.quick
def test_wrapper_run_ingest_fused_vs_composed(monkeypatch):
    """``run_ingest`` with the plan static ON == OFF (the composed
    oracle through the SAME entry point), f32 bitwise."""
    lens = [40, 7, 130, 0, 65]
    w, qo, kvp, kvi, last, q, k, v = _wrapper_setup(lens, monkeypatch)
    npages = int(kvp[-1])
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.float32)
    outs = {}
    for mode in (True, False):
        w.plan(qo, kvp, kvi, last, HQ, HKV, D, PS, causal=True,
               kv_lens=np.asarray(lens), fused_ingest=mode)
        o, (kc, vc) = w.run_ingest(q, k, v, (z(), z()))
        outs[mode] = (np.asarray(o), kc, vc)
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, outs[True][1]),
        _valid_cache_rows(kvp, kvi, lens, outs[False][1]))
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, outs[True][2]),
        _valid_cache_rows(kvp, kvi, lens, outs[False][2]))


def test_wrapper_run_ingest_int8_cache_bits(monkeypatch):
    lens = [40, 33]
    w, qo, kvp, kvi, last, q, k, v = _wrapper_setup(lens, monkeypatch)
    npages = int(kvp[-1])
    z = lambda: jnp.zeros((npages, HKV, PS, D), jnp.int8)
    outs = {}
    for mode in (True, False):
        w.plan(qo, kvp, kvi, last, HQ, HKV, D, PS, causal=True,
               kv_lens=np.asarray(lens), fused_ingest=mode)
        o, (kc, vc) = w.run_ingest(q, k, v, (z(), z()),
                                   k_scale=0.05, v_scale=0.04)
        outs[mode] = (np.asarray(o), kc, vc)
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        _valid_cache_rows(kvp, kvi, lens, outs[True][1]),
        _valid_cache_rows(kvp, kvi, lens, outs[False][1]))


def test_wrapper_run_ingest_errors(monkeypatch):
    lens = [40, 33]
    w, qo, kvp, kvi, last, q, k, v = _wrapper_setup(lens, monkeypatch)
    npages = int(kvp[-1])
    w.plan(qo, kvp, kvi, last, HQ, HKV, D, PS, causal=True,
           kv_lens=np.asarray(lens), fused_ingest=True)
    zi = jnp.zeros((npages, HKV, PS, D), jnp.int8)
    with pytest.raises(ValueError, match="k_scale/v_scale"):
        w.run_ingest(q, k, v, (zi, zi))
    zf = jnp.zeros((npages, HKV, PS, D), jnp.float32)
    with pytest.raises(ValueError, match="raw rows"):
        w.run_ingest(q, k[:10], v[:10], (zf, zf))
    with pytest.raises(RuntimeError, match="plan"):
        type(w)(kv_layout="HND").run_ingest(q, k, v, (zf, zf))


# ---------------------------------------------------------------------------
# rope_quantize_fp8_append_paged_kv_cache reroute
# ---------------------------------------------------------------------------


def _reroute_args(seed=0):
    # whole-page runs: page-aligned start AND end (the gate's contract
    # — a partial last page would zero rows the composed tier keeps)
    # and each run's flat-axis base on INGEST_KV_ALIGN (the compiled
    # kernel's raw-row DMA alignment, a second gate of the reroute)
    lens = np.array([32, 16, 16])
    pos0 = np.array([0, 16, 0])
    kv_indptr = np.array([0, 4, 8, 12], np.int32)
    kv_indices = np.arange(12, dtype=np.int32)
    bi = np.repeat(np.arange(3), lens).astype(np.int32)
    pos = np.concatenate(
        [np.arange(n) + p for n, p in zip(lens, pos0)]).astype(np.int32)
    T = int(lens.sum())
    key = jax.random.PRNGKey(seed)
    DD = 128  # full-head rotary at the reroute's production head_dim
    q = jax.random.normal(key, (T, HQ, DD), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (T, HKV, DD),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (T, HKV, DD),
                          jnp.float32)
    from flashinfer_tpu.rope import generate_cos_sin_cache

    csc = generate_cos_sin_cache(64, DD)
    return q, k, v, csc, pos, kv_indices, kv_indptr, bi


@pytest.mark.quick
def test_reroute_fused_vs_composed_bitwise(monkeypatch):
    """The fused-ingest reroute writes EXACTLY the composed tier's
    cache bits and q output (the oracle stays live via the backend
    gate), and the fused kernel actually ran."""
    from flashinfer_tpu import rope as rope_mod
    from flashinfer_tpu.ops import paged_prefill as pp

    q, k, v, csc, pos, kvi, kvp, bi = _reroute_args()
    calls = []
    real = pp.fused_paged_prefill_ingest

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    def run(backend):
        monkeypatch.setenv("FLASHINFER_TPU_BACKEND", backend)
        kc = jnp.zeros((12, HKV, PS, 128), jnp.float8_e4m3fn)
        vc = jnp.zeros((12, HKV, PS, 128), jnp.float8_e4m3fn)
        return rope_mod.rope_quantize_fp8_append_paged_kv_cache(
            q, k, None, None, v, csc, jnp.asarray(pos), (kc, vc),
            jnp.asarray(kvi), jnp.asarray(kvp), jnp.asarray(bi),
            jnp.asarray(pos), kv_layout="HND", quant_scale_q=0.4,
            quant_scale_kv=0.5)

    monkeypatch.setattr(pp, "fused_paged_prefill_ingest", spy)
    qf, (kcf, vcf) = run("pallas")
    assert calls, "geometry qualified but the reroute did not fire"
    qc, (kcc, vcc) = run("xla")  # the composed oracle tier
    np.testing.assert_array_equal(np.asarray(qf).view(np.uint8),
                                  np.asarray(qc).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(kcf).view(np.uint8),
                                  np.asarray(kcc).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(vcf).view(np.uint8),
                                  np.asarray(vcc).view(np.uint8))


def test_reroute_geometry_gates(monkeypatch):
    """Geometries outside the fused contract stay on the composed
    tier: NHD layout, a non-default cos/sin cache, and mid-page append
    starts must never reach the fused kernel; MLA (``v is None``)
    exits BEFORE the reroute by contract."""
    from flashinfer_tpu import rope as rope_mod
    from flashinfer_tpu.ops import paged_prefill as pp

    monkeypatch.setenv("FLASHINFER_TPU_BACKEND", "pallas")
    q, k, v, csc, pos, kvi, kvp, bi = _reroute_args()
    calls = []
    real = pp.fused_paged_prefill_ingest

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pp, "fused_paged_prefill_ingest", spy)

    def run(layout="HND", cache=None, positions=pos):
        kc = jnp.zeros((12, HKV, PS, 128) if layout == "HND"
                       else (12, PS, HKV, 128), jnp.float8_e4m3fn)
        vc = jnp.zeros_like(kc)
        return rope_mod.rope_quantize_fp8_append_paged_kv_cache(
            q, k, None, None, v, cache if cache is not None else csc,
            jnp.asarray(pos), (kc, vc), jnp.asarray(kvi),
            jnp.asarray(kvp), jnp.asarray(bi), jnp.asarray(positions),
            kv_layout=layout, quant_scale_q=0.4, quant_scale_kv=0.5)

    run(layout="NHD")
    assert not calls  # NHD: composed
    run(cache=csc * 1.0001)
    assert not calls  # non-default cos/sin cache: composed
    shifted = pos.copy()
    shifted[:] = pos + 3  # mid-page starts
    run(positions=shifted)
    assert not calls
    # mid-page END: the whole-page write-back would zero live rows a
    # longer cached sequence still owns — must stay composed (the
    # interior re-append hazard)
    drop = np.ones(pos.shape[0], bool)
    drop[23] = False  # run 0 now ends at position 22 (mid-page)
    q2, k2, v2 = q[drop], k[drop], v[drop]
    kc = jnp.zeros((12, HKV, PS, 128), jnp.float8_e4m3fn)
    rope_mod.rope_quantize_fp8_append_paged_kv_cache(
        q2, k2, None, None, v2, csc, jnp.asarray(pos[drop]),
        (kc, jnp.zeros_like(kc)), jnp.asarray(kvi), jnp.asarray(kvp),
        jnp.asarray(bi[drop]), jnp.asarray(pos[drop]),
        kv_layout="HND", quant_scale_q=0.4, quant_scale_kv=0.5)
    assert not calls
    with pytest.raises(NotImplementedError, match="MLA"):
        rope_mod.rope_quantize_fp8_append_paged_kv_cache(
            q[:, 0], k[:, 0], None, None, None, csc, jnp.asarray(pos),
            (jnp.zeros((12, HKV, PS, 128), jnp.float8_e4m3fn),) * 2,
            jnp.asarray(kvi), jnp.asarray(kvp), jnp.asarray(bi),
            jnp.asarray(pos), kv_layout="HND")
    assert not calls  # the MLA exit precedes the reroute


# ---------------------------------------------------------------------------
# cost model chooser + acceptance bar
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_chooser_and_headline_byte_drop():
    """The ISSUE 14 acceptance bar: headline prefill shapes drop >= 20%
    of modeled HBM bytes, and the chooser prices the separate path as
    three SEQUENTIAL launches (rope/append passes cannot hide under
    the attention MXU floor) so compute-bound shapes still fuse."""
    from flashinfer_tpu.obs import costmodel, hwspec

    for tq, tkv in ((8 * 512, 8 * 4096), (8192, 8192)):
        bd = costmodel.prefill_ingest_breakdown(tq, tkv, 32, 8, 128)
        assert bd["avoided_fraction"] >= 0.20
        assert bd["separate_bytes"] == pytest.approx(
            bd["rope_bytes"] + bd["append_bytes"]
            + bd["attention_bytes"])
        for chip in ("v5e", "v5p"):
            spec = hwspec.spec(chip)
            use, ev = costmodel.predict_prefill_ingest_win(
                tq, tkv, 32, 8, 128, hbm_tbps=spec.hbm_tbps,
                peak_tflops=spec.peak_tflops("bf16"))
            assert use  # the two deleted memory passes clear the 2% bar
            assert ev["fused_s"] < ev["separate_s"]
    # a (hypothetical) chip so compute-starved the memory passes are
    # noise keeps the proven composition via the 2% bar
    use, _ = costmodel.predict_prefill_ingest_win(
        4096, 4096, 32, 8, 128, hbm_tbps=1e6, peak_tflops=1e-3)
    assert not use


def test_ingest_cost_family_stats_form():
    """costmodel.prefill_ingest: launched work from live plan stats,
    effective work the attended pairs — effective <= launched, and the
    byte side is the fused single-pass traffic."""
    from flashinfer_tpu.obs import costmodel

    lens = [64, 64]
    qo, kvp, kvi, _q, _k, _v = _setup(lens, seed=9)
    plan = build_prefill_ingest_units(
        qo, kvp, kvi, np.asarray(lens, np.int64), block_q=BQ,
        pages_per_chunk=PPC, page_size=PS, causal=True)
    c = costmodel.prefill_ingest(
        128, 128, HQ, HKV, D, stats=plan["stats"], block_q=BQ,
        pages_per_chunk=PPC, page_size=PS)
    assert c.op == "prefill_ingest"
    assert c.flops_effective <= c.flops
    alg = costmodel.prefill_ingest(128, 128, HQ, HKV, D)
    assert alg.bytes_read + alg.bytes_written == pytest.approx(
        costmodel.prefill_ingest_breakdown(
            128, 128, HQ, HKV, D)["fused_bytes"])
    # the A/B's separate-mode rows: same op family + FLOPs (the same
    # work executes, split over three launches), three-pass traffic
    sep = costmodel.prefill_ingest_separate(128, 128, HQ, HKV, D)
    assert sep.op == "prefill_ingest"
    assert sep.flops == pytest.approx(alg.flops)
    assert sep.flops_effective == pytest.approx(alg.flops_effective)
    assert sep.bytes_read + sep.bytes_written == pytest.approx(
        costmodel.prefill_ingest_breakdown(
            128, 128, HQ, HKV, D)["separate_bytes"])


# ---------------------------------------------------------------------------
# serving adoptions
# ---------------------------------------------------------------------------


def _engine_run(fused, backend, prompts, *, max_new_tokens=4, **statics):
    """Serve ``prompts`` through a small engine; (tokens by request, the
    engine, the plan bundles its steps built)."""
    import flashinfer_tpu.serve.engine_kernels as ek
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve.engine import (EngineConfig, EngineRequest,
                                             ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    bundles = []
    orig = ek.build_engine_work_units

    def spy(*a, **kw):
        bundles.append(orig(*a, **kw))
        return bundles[-1]

    ek.build_engine_work_units = spy
    try:
        eng = ServingEngine(cfg, params, EngineConfig(**dict(
            dict(num_pages=64, page_size=8, max_batch=4,
                 prefill_budget_tokens=32, max_seq_tokens=64),
            attention_backend=backend, fused_ingest=fused,
            enable_prefix_cache=False, **statics)))
        for i, n in enumerate(prompts):
            eng.submit(EngineRequest(f"r{i}", list(range(1, n + 1)),
                                     max_new_tokens=max_new_tokens))
        out = eng.run()
    finally:
        ek.build_engine_work_units = orig
    return dict(sorted(out.items())), eng, bundles


@pytest.mark.parametrize("fused", ["on", "off"])
def test_engine_ingest_token_parity_and_trace_budget(fused):
    """The engine takes ``fused_ingest`` and ignores it (PR 29): under "on"
    and "off" the kernel tier serves tokens bitwise equal to the reference
    tier, no plan bundle carries an ``ingest`` plan (16 + 16 is the
    from-scratch, aligned first step the ingest launch used to take), and a
    rung traces once."""
    prompts = [16, 16, 19]
    got, eng, bundles = _engine_run(fused, "kernel", prompts)
    ref, _, _ = _engine_run("off", "reference", prompts)
    assert got == ref
    assert eng._ingest is False
    assert bundles and not any(
        "ingest" in b or "ingest_on" in b for b in bundles)
    assert all(n == 1 for n in eng._rung_traced.values())
    assert eng.num_traces == len(eng._rung_traced) <= 9


def test_engine_lowered_step_ignores_ingest_knob():
    """``fused_ingest="on"`` and ``"off"`` engines lower the same program,
    rung by rung."""
    import flashinfer_tpu.serve.engine_kernels as ek
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve.engine import EngineConfig, ServingEngine

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)

    def lowered(fused):
        ec = EngineConfig(num_pages=64, page_size=8, max_batch=4,
                          prefill_budget_tokens=32, max_seq_tokens=64,
                          attention_backend="kernel", fused_ingest=fused)
        eng = ServingEngine(cfg, params, ec)
        texts = []
        for rung in ec.rungs():
            plans = ek.build_engine_work_units(
                [ek.SchedSeg(row0=0, n=rung, pages=tuple(range(1, 9)),
                             split=0, kv_after=rung, decoding=False, slot=0,
                             group=0)], rung=rung, geom=eng._geom)
            B, ppr = ec.max_batch, eng._ppr
            args = (params, *(np.zeros(rung, np.int32),) * 5,
                    *(np.zeros((B, ppr), np.int32),) * 2,
                    *(np.zeros(rung, np.int32),) * 2,
                    *(np.zeros(B, np.int32),) * 2,
                    ek.plans_to_device(plans), eng.caches)
            texts.append(eng._step.lower(*args).as_text())
        return texts

    assert lowered("on") == lowered("off")


def test_engine_serves_lone_short_prefill_with_ingest_on():
    """PERF.md section 7.5 (found in PR 23): a lone fresh prompt shorter than
    its rung, of a length that is no multiple of 16, at an idle kernel-tier
    engine built with ``fused_ingest="on"`` tripped the ingest planner's
    ``ingest_kv_aligned`` assertion.  The planner is gone; the step runs and
    serves what the reference tier serves."""
    statics = dict(max_new_tokens=3, max_batch=8, prefill_budget_tokens=56,
                   max_seq_tokens=128)       # 40 rows ride the rung of 64
    got, eng, _ = _engine_run("on", "kernel", [40], **statics)
    ref, _, _ = _engine_run("off", "reference", [40], **statics)
    assert got == ref and len(got["r0"]) == 3
    assert 64 in eng._rung_traced
    assert eng.num_traces == len(eng._rung_traced)


def test_engine_config_validates_ingest_knob():
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve.engine import EngineConfig, ServingEngine

    cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="fused_ingest"):
        ServingEngine(cfg, params, EngineConfig(
            num_pages=16, fused_ingest="maybe"))


# ---------------------------------------------------------------------------
# analysis-registration skew + observability schema
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_analysis_registrations_match_real_modules():
    """The L007/L009 registrations (the PR 4 NOTE: unregistered
    surfaces are silently skipped) cannot skew from the real modules:
    planner + kernel + launcher exist with the names registered, and
    the knob is registered with the choices the configs ship."""
    from flashinfer_tpu import autotuner
    from flashinfer_tpu.analysis.pallas_contract import PLANNER_KERNELS
    from flashinfer_tpu.analysis.vmem_budget import KNOB_LAUNCHES
    from flashinfer_tpu.ops import paged_prefill as pp

    assert PLANNER_KERNELS["build_prefill_ingest_units"] == \
        "_fused_prefill_ingest_kernel"
    assert callable(getattr(pp, "build_prefill_ingest_units"))
    assert callable(getattr(pp, "_fused_prefill_ingest_kernel"))
    assert KNOB_LAUNCHES["prefill.fused_ingest"].launcher == \
        "fused_paged_prefill_ingest"
    assert callable(getattr(pp, "fused_paged_prefill_ingest"))
    spec = autotuner.KNOWN_KNOBS["prefill.fused_ingest"]
    assert spec.kind == "str" and set(spec.choices) == {"off", "on"}


def test_tuning_config_ingest_sections_valid():
    """The shipped v5e/v5p prefill_ingest seed sections are L006-valid
    against the REAL registry (key parses, knob known, value in
    choices) and stay seed-labeled until an on-chip sweep lands."""
    import json
    import os

    import flashinfer_tpu
    from flashinfer_tpu import autotuner

    cfg_dir = os.path.join(os.path.dirname(flashinfer_tpu.__file__),
                           "tuning_configs")
    for gen in ("v5e", "v5p"):
        data = json.load(open(os.path.join(cfg_dir, f"{gen}.json")))
        sec = data["prefill_ingest"]
        assert sec["seed"] is True
        assert sec["seed_keys"]
        for key, val in sec["tactics"].items():
            op = key.split("|", 1)[0]
            assert op == "prefill.fused_ingest"
            assert autotuner.validate_tactic(op, val) is None


def test_stamp_row_ingest_identity_and_measurement():
    """roofline.stamp_row: ``fused_ingest`` is an identity field (A/B
    rows never compete with banked history — the step_mode precedent)
    and ``ingest_bytes_avoided`` a measurement field the auditor
    accepts."""
    from flashinfer_tpu.obs import bench_audit, costmodel, hwspec, roofline

    cost = costmodel.prefill_ingest(512, 4096, 32, 8, 128)
    row = {"phase": "prefill", "kind": "paged"}
    roofline.stamp_row(row, cost, 1e-3, hwspec.spec("v5e"),
                       fused_ingest=True, ingest_bytes_avoided=1.5e8)
    assert row["fused_ingest"] is True
    assert row["ingest_bytes_avoided"] == 1.5e8
    assert "ingest_bytes_avoided" in bench_audit.MEASUREMENT_FIELDS
    assert "fused_ingest" not in bench_audit.MEASUREMENT_FIELDS


def test_perf_report_prefill_ingest_section():
    """obs perf (perf/6): the prefill_ingest section joins the
    predicted byte drop with stamped ingest rows, and the headline
    cells all clear the >= 20% acceptance bar."""
    from flashinfer_tpu.obs import roofline

    pred = roofline.predict_prefill_ingest()
    assert pred
    for cell in pred.values():
        assert cell["avoided_fraction"] >= 0.20
        assert cell["chips"]
    report = roofline.build_perf_report([])
    assert report["schema"].endswith("/6")
    assert "prefill_ingest" in report
    text = roofline.render_perf_report(report)
    assert "prefill-ingest" in text
